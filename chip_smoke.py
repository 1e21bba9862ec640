#!/usr/bin/env python3
"""Chip smoke: the flagship trainer peer takes three swarm epochs on the TPU.

The quickest proof that the system's main path still starts on the chip.
One process holds the accelerator and runs the trainer exactly as a user
would — ``dalle_tpu.cli.run_trainer`` -> ``TrainingTask`` -> ``train_loop``
-> ``CollaborativeOptimizer`` at the full flagship shape
(``--preset flagship``, no model field overridden) with random weights from
the seed — against one CPU child, ``run_aux_peer --assist-in-averaging``,
which owns an all-reduce part at weight 0 and is the bootstrap address, so
the gradient rounds are really exchanged through the device wire codec.

It fails (exit code != 0, no result line) unless: JAX's backend is the TPU;
the lowered grad step holds Mosaic calls of the axial, window, LayerNorm and
GEGLU kernels and the apply step the LAMB quantizer (a dispatcher that gave
way to XLA is a red run); the u4 wire quantizer is byte-equal to the host
codec on a 1M-element vector; warmup and every epoch loss is finite; the
last epoch is reached; at least two epochs were exchanged in a group of two
with a non-zero all-reduce and reduce/gather hops; no round fell back to
local gradients; every device was used. Timings it prints are orientation,
not a benchmark. The last line of stdout is the result:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage:  python3 chip_smoke.py            (needs the TPU; ~1200 s budget)
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import logging
import math
import os
import re
import socket
import subprocess
import sys
import time
from pathlib import Path
from typing import Dict, Optional, Sequence

REPO = Path(__file__).resolve().parent

# kernel family -> Mosaic kernel names (pallas_call names the custom call
# after the kernel function) that must appear in the lowered program
GRAD_STEP_KERNELS = {
    "axial attention": ("_fwd_kernel", "_bwd_kernel"),
    "window attention": ("_win_fwd_kernel", "_win_bwd_kernel"),
    "LayerNorm": ("_ln_fwd_kernel", "_ln_bwd_kernel"),
    "GEGLU feed-forward": ("_ff_fwd_kernel", "_ff_bwd_kernel"),
}
APPLY_STEP_KERNELS = {"LAMB quantizer": ("_quant_kernel",)}


class SmokeFailure(AssertionError):
    """A phase of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def kernel_census(lowered_text: str) -> collections.Counter:
    """Mosaic custom calls in a lowered program, counted by kernel name."""
    return collections.Counter(
        re.findall(r'kernel_name = "([^"]+)"', lowered_text))


def check_kernels(program: str, census: collections.Counter,
                  families: Dict[str, Sequence[str]]) -> None:
    for family, names in families.items():
        missing = [n for n in names if not census.get(n)]
        check(not missing,
              f"{program}: no Mosaic call of {missing} ({family} kernel) in "
              f"the lowered program — the dispatcher gave way to XLA; "
              f"found {dict(census)}")


def lowered_steps(task):
    """The task's two jitted programs lowered on abstract operands of the
    shapes the loop feeds them (no parameters are allocated). Operand
    shardings are left to the compiler: which kernels a program holds is
    decided by shapes, backend and mesh, all the task's own."""
    import jax
    import jax.numpy as jnp

    from dalle_tpu.models.dalle import init_params
    from dalle_tpu.training.steps import TrainState

    cfg = task.model_cfg
    params = jax.eval_shape(
        lambda: init_params(task.model, jax.random.PRNGKey(0)))
    state = jax.eval_shape(lambda p: TrainState.create(p, task.tx), params)
    grads = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)
    b = task.local_batch_size
    batch = {"text": jax.ShapeDtypeStruct((b, cfg.text_seq_len), jnp.int32),
             "image": jax.ShapeDtypeStruct((b, cfg.image_seq_len),
                                           jnp.int32)}
    return (task.grad_step.lower(params, batch).as_text(),
            task.apply_step.lower(state, grads).as_text())


def check_u4_parity(interpret: bool) -> None:
    """One direct call of the u4 wire quantizer (not on the default wire
    path, so nothing else would compile it) against the host codec on a
    1M-element vector. Byte-equal wherever f32 division is correctly
    rounded (CPU, interpret mode); the TPU's divide is not, so there a
    scale may sit an ulp off and a value on a rounding boundary may land
    one level away — anything more is a broken kernel."""
    import jax
    import numpy as np

    from dalle_tpu.ops.pallas.quant_kernels import (WIRE_QBLOCK4,
                                                    wire_quantize_u4_pallas)
    from dalle_tpu.swarm import compression

    x = np.random.default_rng(0).standard_normal(1 << 20).astype(np.float32)
    codes, scales = jax.device_get(
        wire_quantize_u4_pallas(jax.numpy.asarray(x), interpret=interpret))
    host = compression.compress_u4(x)
    n_blocks = x.size // WIRE_QBLOCK4
    host_scales = np.frombuffer(host, np.float32, n_blocks, offset=4)
    packed = np.frombuffer(host, np.uint8, offset=4 + 4 * n_blocks)
    host_codes = np.stack([packed & 0x0F, packed >> 4], axis=1).reshape(-1)
    ulps = np.abs(scales.view(np.int32).astype(np.int64)
                  - host_scales.view(np.int32))
    levels = np.abs(codes.astype(np.int16) - host_codes)
    print(f"u4 wire quantizer vs host codec, {x.size} elements: "
          f"{int((levels > 0).sum())} codes differ (max {int(levels.max())} "
          f"level), {int((ulps > 0).sum())}/{n_blocks} scales differ "
          f"(max {int(ulps.max())} ulp)", flush=True)
    check(levels.max() <= 1 and (levels > 0).mean() <= 1e-3
          and ulps.max() <= 1,
          "wire_quantize_u4_pallas disagrees with the host u4 codec beyond "
          "the division's last ulp")
    check(not interpret or (levels.max() == 0 and ulps.max() == 0),
          "wire_quantize_u4_pallas is not byte-equal to the host u4 codec "
          "in interpret mode")


class _Records(logging.Handler):
    """Keeps the log records of one logger subtree for the assertions."""

    def __init__(self, level: int):
        super().__init__(level)
        self.records = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(record)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_listening(port: int, proc: subprocess.Popen, log: Path,
                    timeout: float = 180.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        check(proc.poll() is None,
              f"the aux peer exited rc={proc.returncode} before listening; "
              f"see {log}")
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=1.0):
                return
        except OSError:
            time.sleep(0.2)
    raise SmokeFailure(f"aux peer port {port} never came up in {timeout}s")


def _stop(child: subprocess.Popen) -> None:
    child.terminate()
    try:
        child.wait(timeout=20)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()


def check_epochs(rows: Sequence[dict], max_epochs: int,
                 warnings: Sequence[logging.LogRecord]) -> None:
    """The trainer's epoch rows (``--metrics-file``) and WARNING records
    must show finite losses, the last epoch reached, at least two really
    exchanged rounds, and no round that fell back to local gradients."""
    for row in rows:
        t = row["timings"]
        print(f"epoch {row['epoch']}: loss {row['loss']:.4f} "
              f"group {t.get('group_size')} "
              f"matchmaking {t.get('matchmaking_s')} s "
              f"allreduce {t.get('allreduce_s')} s "
              f"round wall {t.get('hidden_s')} s "
              f"apply {t.get('apply_s')} s hops {t.get('round_hops')} "
              f"steps overlapped {t.get('overlapped_steps')}")
    check(bool(rows) and rows[-1]["epoch"] >= max_epochs,
          f"epoch {max_epochs} not reached: {[r['epoch'] for r in rows]}")
    check(all(math.isfinite(r["loss"]) for r in rows),
          f"non-finite epoch loss: {[r['loss'] for r in rows]}")
    failed = [r.getMessage() for r in warnings
              if "applying local gradients" in r.getMessage()]
    check(not failed, f"a round fell back to local gradients: {failed}")

    def exchanged(t: dict) -> bool:
        hops = t.get("round_hops") or {}
        return (t.get("group_size") == 2 and t.get("allreduce_s", 0) > 0
                and hops.get("reduce", 0) > 0 and hops.get("gather", 0) > 0)
    n_exchanged = sum(exchanged(r["timings"]) for r in rows)
    check(n_exchanged >= 2,
          f"only {n_exchanged} epoch(s) exchanged in a group of 2 with "
          f"reduce and gather hops")


def run_smoke(out_dir: Path, *, preset: str = "flagship",
              per_device_batch: int = 4, grad_accum_steps: int = 8,
              max_epochs: int = 3, matchmaking_time: float = 5.0,
              require_backend: Optional[str] = "tpu",
              interpret_kernels: bool = False,
              trainer_args: Sequence[str] = ()) -> dict:
    """Run the smoke; returns the device description on success and raises
    on any miss. ``require_backend=None`` lifts the backend check and
    ``interpret_kernels`` runs the Pallas kernels in interpret mode — what
    the CPU-tier test of this plumbing uses (Mosaic calls cannot be counted
    there: interpreted kernels lower to plain HLO)."""
    out_dir.mkdir(parents=True, exist_ok=True)
    metrics_file = out_dir / "trainer_epochs.jsonl"
    metrics_file.unlink(missing_ok=True)
    aux_log = out_dir / "aux_peer.log"
    logging.basicConfig(
        level="INFO",
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    # -- before this process touches JAX ----------------------------------
    # build the native DHT library ONCE: its build lock is a thread lock,
    # two processes racing `make` from a clean tree corrupt the .so
    from dalle_tpu.swarm import _native
    from dalle_tpu.utils.compile_cache import enable_compile_cache
    _native.load()
    cache_dir = enable_compile_cache()

    port = _free_port()
    swarm_args = ["--matchmaking-time", str(matchmaking_time)]
    child_env = dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=str(REPO) + os.pathsep
                     + os.environ.get("PYTHONPATH", ""))
    with contextlib.ExitStack() as cleanup:
        with open(aux_log, "w") as aux_out:
            child = subprocess.Popen(
                [sys.executable, "-m", "dalle_tpu.cli.run_aux_peer",
                 "--platform", "cpu", "--preset", preset,
                 "--assist-in-averaging", "--port", str(port),
                 "--refresh-period", "5", *swarm_args],
                env=child_env, cwd=REPO, stdout=aux_out,
                stderr=subprocess.STDOUT)
        cleanup.callback(_stop, child)
        _wait_listening(port, child, aux_log)

        import jax

        from dalle_tpu.cli import run_trainer
        from dalle_tpu.models import attention
        from dalle_tpu.swarm.device_codec import resolve_backend
        from dalle_tpu.task import TrainingTask

        backend = jax.default_backend()
        check(require_backend is None or backend == require_backend,
              f"jax.default_backend() is {backend!r}, "
              f"need {require_backend!r}")
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        print(f"jax {jax.__version__} device {device} "
              f"compile cache {cache_dir}", flush=True)

        warnings = _Records(logging.WARNING)
        loop_log = _Records(logging.INFO)
        for name, handler in (("dalle_tpu", warnings),
                              ("dalle_tpu.training.loop", loop_log)):
            logging.getLogger(name).addHandler(handler)
            cleanup.callback(logging.getLogger(name).removeHandler, handler)
        cleanup.callback(setattr, attention, "_PALLAS_INTERPRET",
                         attention._PALLAS_INTERPRET)
        attention._PALLAS_INTERPRET = interpret_kernels

        argv = ["--preset", preset,
                "--per-device-batch", str(per_device_batch),
                "--grad-accum-steps", str(grad_accum_steps),
                "--max-epochs", str(max_epochs),
                "--initial-peers", f"127.0.0.1:{port}",
                "--metrics-file", str(metrics_file),
                *swarm_args, *trainer_args]
        args = run_trainer.build_parser().parse_args(argv)
        task = TrainingTask(*run_trainer.configs_from_args(args))
        # two local batches an epoch: the round launched by the second
        # overlaps the next epoch's accumulation (delay_optimizer_step)
        argv += ["--target-batch-size", str(2 * task.local_batch_size)]
        print(f"mesh {dict(task.mesh.shape)} local batch "
              f"{task.local_batch_size}", flush=True)
        check(resolve_backend(task.collab_cfg.wire_codec_backend)
              == "device", "the wire codec did not resolve to the device")

        if not interpret_kernels:
            grad_text, apply_text = lowered_steps(task)
            for program, text, families in (
                    ("grad step", grad_text, GRAD_STEP_KERNELS),
                    ("apply step", apply_text, APPLY_STEP_KERNELS)):
                census = kernel_census(text)
                print(f"{program}: {text.count('tpu_custom_call')} Mosaic "
                      f"calls {dict(census)}", flush=True)
                check_kernels(program, census, families)
        check_u4_parity(interpret_kernels)
        del task

        t0 = time.monotonic()
        rc = run_trainer.main(argv)
        run_s = time.monotonic() - t0
        check(rc == 0, f"run_trainer.main returned {rc}")
        check(child.poll() is None,
              f"the aux peer died (rc={child.returncode}); see {aux_log}")
        check_epochs([json.loads(line) for line
                      in metrics_file.read_text().splitlines()],
                     max_epochs, warnings.records)

        steps = [r.args[3] for r in loop_log.records
                 if str(r.msg).startswith("warmup %d/%d")]
        print(f"warmup grad steps (first compiles) {steps} s; "
              f"whole run {run_s:.1f} s")
        # the trainer's own account of its set-up, made when its first
        # step closed (dalle_tpu/obs/compiles.py), and what its compile
        # counter saw after that
        from dalle_tpu.obs import compiles, default_tracer
        for row in default_tracer().dump():
            if row["phase"] == compiles.ACCOUNT_EVENT:
                print(compiles.account_line(row["a"]))
        print("compiles after the first step: "
              f"{[c[0] for c in compiles.installed().after_first_step]}")
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        print(f"peak_bytes_in_use per device: {peaks}")
        # backends that report memory must show every device used (the
        # batch split over the chips, not replicated on the first)
        check(all(p is None or p > 0 for p in peaks),
              f"a device was never used: {peaks}")
        return device


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--out-dir", type=Path,
                        default=REPO / "chiprun_out" / "chip_smoke",
                        help="epoch rows and the aux peer's log land here")
    args = parser.parse_args(argv)
    device = run_smoke(args.out_dir)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
