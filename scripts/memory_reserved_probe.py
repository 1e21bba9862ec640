"""What is ``bytes_reserved``, and when does the allocator give a buffer
back? One chip experiment on a cell's real task (OBSERVABILITY.md, "A full
chip says what holds it"; PERF.md section 6, PR 41).

    python3 scripts/memory_reserved_probe.py [--workload <cell>] [--seed N]
        [--out chiprun_out/memory_probe]

It builds the cell's ``TrainingTask`` as the benchmark does (no reference
check) and reads the read device's ``memory_stats()``

1. with the train state on the device, before the grad step is lowered,
2. after it is lowered, and after it is compiled (nothing has run),
3. **while it runs**, from a second thread every 2 ms, and when the
   dispatch returns,
4. after it returned, after its output is dropped,
5. around the swarm optimizer's accumulate, as written (no donation) and
   with the accumulator donated: at the dispatch's return, then every
   0.2 ms until the allocator's reading has stood still for 30 ms,
6. over four steps of the loop's own order (dispatch, loss, accumulate),
   at the loop's sampling points,
7. 2 000 times in a row, and the task's memory account's four readings
   of a step as often: what the account costs a step.

One JSON object: a line on standard output, the timelines in ``--out``.
It fails without a TPU.
"""
import argparse
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

KEYS = ("bytes_in_use", "bytes_reserved", "peak_bytes_in_use",
        "peak_bytes_reserved", "largest_alloc_size", "bytes_limit",
        "num_allocs", "largest_free_block_bytes", "bytes_reservable_limit")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", default="trinitymini-train-solo")
    parser.add_argument("--seed", type=int, default=4100000001)
    parser.add_argument("--out", type=Path,
                        default=ROOT / "chiprun_out" / "memory_probe")
    args = parser.parse_args()

    from benchmark import harness
    from benchmark.manifest import Manifest
    from dalle_tpu.swarm import _native
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    _native.load()
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    if jax.default_backend() != "tpu":
        print("memory_reserved_probe: needs a TPU", file=sys.stderr)
        return 3
    from dalle_tpu.cli import run_trainer
    from dalle_tpu.task import TrainingTask

    cell = Manifest().cell(args.workload)
    task = TrainingTask(*run_trainer.configs_from_args(
        run_trainer.build_parser().parse_args(
            harness.trainer_argv(cell, args.seed))))
    device = jax.local_devices()[0]
    t0 = time.perf_counter()

    def read():
        stats = device.memory_stats() or {}
        return {"t": round(time.perf_counter() - t0, 6),
                **{k: stats[k] for k in KEYS if k in stats}}

    out = {"workload": cell.name, "device_kind": device.device_kind,
           "all_keys": sorted((device.memory_stats() or {}).keys()),
           "empty": read()}
    state = task.train_state
    jax.block_until_ready(state)
    out["state_on_device"] = read()
    nbytes = lambda tree: sum(x.nbytes for x in jax.tree.leaves(tree))
    out["params_bytes"] = nbytes(state.params)
    out["opt_state_bytes"] = nbytes(state.opt_state)
    out["parameters"] = sum(x.size for x in jax.tree.leaves(state.params))
    batch = next(task.batches())
    out["batch_bytes"] = nbytes(batch)

    # -- 2: lowered, compiled, not run -----------------------------------
    lowered = task.grad_step.lower(state.params, batch)
    out["lowered"] = read()
    compiled = lowered.compile()
    plan = compiled.memory_analysis()
    out["plan"] = {k: int(getattr(plan, k)) for k in (
        "temp_size_in_bytes", "argument_size_in_bytes",
        "output_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes") if hasattr(plan, k)}
    out["compiled"] = read()

    # -- 3, 4: while it runs, from a second thread ------------------------
    def polled(period_s):
        rows, halt = [], threading.Event()

        def poll():
            while not halt.is_set():
                rows.append(read())
                time.sleep(period_s)
        thread = threading.Thread(target=poll, daemon=True)
        thread.start()
        return rows, lambda: (halt.set(), thread.join())

    timelines = {}
    for call in ("first_call", "second_call"):
        rows, stop = polled(0.002)
        time.sleep(0.02)
        grads, metrics = task.grad_step(state.params, batch)
        out[f"{call}_dispatched"] = read()
        float(metrics["loss"])
        out[f"{call}_returned"] = read()
        time.sleep(0.02)
        stop()
        timelines[call] = rows
        out[f"{call}_while_running_max"] = {
            k: max(r.get(k, 0) for r in rows) for k in KEYS[:5]}
        if call == "first_call":
            out["step_output_bytes"] = nbytes((grads, metrics))
            del grads, metrics
            time.sleep(0.05)
            out["output_dropped"] = read()

    # -- 5: the accumulate, as written and donated ------------------------
    from dalle_tpu.swarm.optimizer import accumulate_grads as add
    for name, fn in (("accumulate", jax.jit(add)),
                     ("accumulate_donated", jax.jit(add, donate_argnums=0))):
        acc = jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32,
                                               device=g.sharding), grads)
        acc = fn(acc, grads, 1.0)          # compiled, and run once
        jax.block_until_ready(acc)
        time.sleep(0.05)
        rows = [dict(read(), at="before")]
        t_call = time.perf_counter()
        acc = fn(acc, grads, 1.0)
        rows.append(dict(read(), at="dispatch returned",
                         dispatch_s=round(time.perf_counter() - t_call, 6)))
        still, last = time.perf_counter(), rows[-1].get("bytes_in_use", 0)
        while time.perf_counter() - still < 0.03:
            row = read()
            if row.get("bytes_in_use", 0) != last:
                rows.append(row)
                still, last = time.perf_counter(), row.get("bytes_in_use", 0)
            time.sleep(0.0002)
        jax.block_until_ready(acc)
        rows.append(dict(read(), at="ready"))
        timelines[name] = rows
        out[name] = {
            "before": rows[0].get("bytes_in_use"),
            "at_dispatch_return": rows[1].get("bytes_in_use"),
            "dispatch_s": rows[1]["dispatch_s"],
            "settled": rows[-1].get("bytes_in_use"),
            "settled_after_s": round(rows[-2]["t"] - rows[1]["t"], 6)
            if len(rows) > 3 else 0.0}
        del acc
    del grads, metrics
    time.sleep(0.05)
    out["before_loop"] = read()

    # -- 6: the loop's own order, at its sampling points ------------------
    accumulate = jax.jit(add)
    acc, grads, steps = None, None, []
    for _ in range(4):
        row = {"edge": read()}
        grads, metrics = task.grad_step(state.params, batch)
        row["after_grad_dispatch"] = read()
        float(metrics["loss"])
        row["after_loss"] = read()
        if acc is None:
            acc = jax.tree.map(lambda g: jnp.zeros(
                g.shape, jnp.float32, device=g.sharding), grads)
        acc = accumulate(acc, grads, 8.0)
        row["after_accumulate"] = read()
        steps.append(row)
    out["loop_steps"] = steps
    out["end"] = read()

    # -- 7: what a step's four readings cost -------------------------------
    account, row = task.memory, type("Row", (), {"set": lambda self, **a: 0})()
    account.start()
    laps = {"memory_stats": lambda: device.memory_stats(),
            "a step's account (four readings, one row)": lambda: (
                account.after_grad((grads, metrics), batch),
                account.settled(), account.after_accumulate(acc),
                account.close_step(row))}
    for name, lap in laps.items():
        lap()
        t = time.perf_counter()
        for _ in range(2000):
            lap()
        out.setdefault("cost_us", {})[name] = round(
            (time.perf_counter() - t) / 2000 * 1e6, 2)

    args.out.mkdir(parents=True, exist_ok=True)
    (args.out / "timelines.json").write_text(json.dumps(timelines))
    (args.out / "probe.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
