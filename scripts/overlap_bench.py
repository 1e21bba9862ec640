"""Overlap bench: how much of the collective does the r19 pipeline hide?

An overlapped round runs behind grad steps (``delay_optimizer_step``,
tests/test_collab.py); this bench measures what r19's per-part pipeline
does to the round itself at the flagship payload (~125.6M unique
params, ~502 MB f32 per peer, the SWARM_SCALE.md regime): N loopback
peers run ONE honest grad round per mode — sequential protocol vs
``pipeline_hops`` — on the pinned u4 wire with error feedback armed,
while a trainer thread per peer burns a bounded accumulate-compute
budget (fixed numpy matmul ticks, emitted as ``accumulate`` spans into
the same flight ring the round's ``ar_hop_*`` spans land in).

Reported per mode (and committed as OVERLAP_BENCH.json):

- ``round_wall_s`` — the ``run_allreduce`` wall (matchmaking excluded);
- ``hidden_s`` — wall-clock covered by accumulate ticks that ran
  strictly inside the round envelope (interval union, not a sum);
- ``exposed_sync_s`` — ``round_wall_s - hidden_s``: the time the
  trainer was BLOCKED on the collective with its compute budget spent.

The gate (ISSUE 19): pipelined ``exposed_sync_s`` at least 30% below
sequential, AND the merged cross-peer timeline contains at least one
``ar_hop_*`` span strictly concurrent with an ``accumulate`` span —
overlap proven from spans, not inferred from totals. (One process,
one monotonic clock: cross-thread span geometry is real here.)

Run:  JAX_PLATFORMS=cpu python scripts/overlap_bench.py \
          [--peers 2] [--budget-s 25] [--elems N] [--depth 2] \
          [--seed 0] [--out OVERLAP_BENCH.json]

``--elems`` swaps the flagship payload for a small synthetic one (the
fast-test path); the committed artifact is the flagship run. On this
one-core box every peer's codec work serializes, so the sequential
round wall is an upper bound — the pipeline's win here is filling the
scatter-barrier and gather waits with useful encode/serve work, which
is exactly the exposed-sync number.
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dalle_tpu.obs.trace import Tracer, merge_rows  # noqa: E402
from dalle_tpu.swarm import DHT, Identity, compression  # noqa: E402
from dalle_tpu.swarm.allreduce import run_allreduce  # noqa: E402
from dalle_tpu.swarm.error_feedback import make_pair  # noqa: E402
from dalle_tpu.swarm.matchmaking import make_group  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- overlap math (unit-tested in tests/test_overlap_bench.py) -------------

def interval_union(intervals):
    """Total length of the union of (start, end) intervals."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def exposed_sync(round_t0, round_dur, acc_spans):
    """(hidden_s, exposed_s): accumulate coverage of the round
    envelope (union of clipped intervals) and the remainder the
    trainer spent blocked on the collective."""
    env_e = round_t0 + round_dur
    clipped = [(max(t0, round_t0), min(t0 + d, env_e))
               for t0, d in acc_spans]
    hidden = interval_union(clipped)
    return hidden, max(0.0, round_dur - hidden)


def find_concurrent_hop(rows):
    """First (hop_row, accumulate_row, overlap_s) pair of spans that
    strictly overlap in wall-clock — the timeline proof that collective
    hops ran WHILE accumulation compute ran. Rows must share a clock
    (one process)."""
    hops = [r for r in rows
            if str(r.get("phase", "")).startswith("ar_hop_")
            and r.get("dur_s", 0) > 0]
    accs = [r for r in rows if r.get("phase") == "accumulate"
            and r.get("dur_s", 0) > 0]
    best = None
    for h in hops:
        h0, h1 = h["t0"], h["t0"] + h["dur_s"]
        for a in accs:
            a0, a1 = a["t0"], a["t0"] + a["dur_s"]
            ov = min(h1, a1) - max(h0, a0)
            if ov > 0 and (best is None or ov > best[2]):
                best = (h, a, ov)
    return best


# -- the bench -------------------------------------------------------------

def _payload(n_peers, seed, elems):
    if elems:
        rng0 = np.random.RandomState(seed)
        base = rng0.randn(elems).astype(np.float32)
        return [[base * (1 + i)] for i in range(n_peers)], elems
    from swarm_payload_bench import flagship_grad_arrays
    grads, total = [], 0
    for i in range(n_peers):
        arrays, total = flagship_grad_arrays(seed + i)
        grads.append(arrays)
    return grads, total


def _accumulate_loop(tracer, trace, budget_s, round_done, tick_elems):
    """Fixed-budget trainer compute: matmul ticks until the budget is
    spent or the round ends; each tick is an ``accumulate`` span."""
    rng = np.random.RandomState(0)
    a = rng.randn(tick_elems, tick_elems).astype(np.float32)
    b = rng.randn(tick_elems, tick_elems).astype(np.float32)
    spent, ticks = 0.0, 0
    while spent < budget_s and not round_done.is_set():
        t0 = time.monotonic()
        (a @ b).sum()
        dur = time.monotonic() - t0
        tracer.add("train", "accumulate", trace, t0, dur, tick=ticks)
        spent += dur
        ticks += 1
    return spent, ticks


def run_mode(nodes, mode, pipelined, grads, budget_s, depth, epoch,
             allreduce_timeout, tick_elems):
    n = len(nodes)
    prefix = "ob"
    trace = f"{prefix}:grads:{epoch}"
    tracers = [Tracer(peer=f"peer{i}", ring_bytes=1024 * 1024)
               for i in range(n)]
    efs = [make_pair() for _ in range(n)]
    reports = [dict() for _ in range(n)]
    walls = [None] * n
    errors = []

    def peer(i):
        try:
            g = make_group(nodes[i], prefix, epoch=epoch, weight=1.0,
                           matchmaking_time=5.0, min_group_size=n)
            assert g is not None and g.size == n, "matchmaking failed"
            round_done = threading.Event()
            acc_out = {}

            def trainer():
                acc_out["spent"], acc_out["ticks"] = _accumulate_loop(
                    tracers[i], trace, budget_s, round_done, tick_elems)

            tt = threading.Thread(target=trainer,
                                  name=f"bench-acc{i}", daemon=True)
            t0 = time.monotonic()
            tt.start()
            try:
                run_allreduce(
                    nodes[i], g, prefix, epoch, grads[i], weight=1.0,
                    allreduce_timeout=allreduce_timeout,
                    codec=compression.UNIFORM4BIT,
                    gather_codec=compression.UNIFORM4BIT,
                    pin_codec=True, ef_scatter=efs[i][0],
                    ef_gather=efs[i][1], report=reports[i],
                    pipeline_hops=pipelined, pipeline_depth=depth,
                    tracer=tracers[i], trace=trace)
            finally:
                round_done.set()
            walls[i] = (t0, time.monotonic() - t0)
            tt.join(timeout=budget_s + 30)
            return acc_out
        except BaseException as e:  # noqa: BLE001
            errors.append((i, e))
            raise

    threads = [threading.Thread(target=peer, args=(i,),
                                name=f"bench-peer{i}")
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"{mode}: peer failures: {errors!r}")

    rows = merge_rows([tr.dump() for tr in tracers])
    peers_out = []
    for i in range(n):
        t0, wall = walls[i]
        acc = [(r["t0"], r["dur_s"]) for r in tracers[i].dump()
               if r.get("phase") == "accumulate"]
        hidden, exposed = exposed_sync(t0, wall, acc)
        hops = reports[i]["phases"].get("hops", [])
        peers_out.append({
            "round_wall_s": round(wall, 3),
            "hidden_s": round(hidden, 3),
            "exposed_sync_s": round(exposed, 3),
            "acc_ticks": len(acc),
            "complete": reports[i]["complete"],
            "hop_rows": len(hops),
            "hop_legs": sorted({r["leg"] for r in hops}),
        })
    wall = float(np.mean([w for _t, w in walls]))
    hidden = float(np.mean([p["hidden_s"] for p in peers_out]))
    exposed = float(np.mean([p["exposed_sync_s"] for p in peers_out]))
    return {
        "mode": mode,
        "pipeline_hops": pipelined,
        "round_wall_s": round(wall, 3),
        "hidden_s": round(hidden, 3),
        "exposed_sync_s": round(exposed, 3),
        "complete": all(p["complete"] for p in peers_out),
        "peers": peers_out,
    }, rows


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--peers", type=int, default=2)
    parser.add_argument("--budget-s", type=float, default=25.0,
                        help="per-round trainer accumulate-compute "
                             "budget (the bounded work the real loop "
                             "has per global step)")
    parser.add_argument("--elems", type=int, default=0,
                        help="synthetic payload elems instead of the "
                             "flagship gradient set (0 = flagship)")
    parser.add_argument("--depth", type=int, default=2,
                        help="pipeline_depth for the pipelined row")
    parser.add_argument("--tick-elems", type=int, default=1024,
                        help="matmul side length of one accumulate "
                             "tick")
    parser.add_argument("--allreduce-timeout", type=float, default=300.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", type=str, default=None)
    args = parser.parse_args(argv)

    grads, total = _payload(args.peers, args.seed, args.elems)
    payload_mb = total * 4 / 1e6
    print(f"payload: {total} elems ({payload_mb:.1f} MB f32/peer), "
          f"{args.peers} peers, u4+EF wire, "
          f"budget {args.budget_s:.0f}s/round")

    nodes = []
    for i in range(args.peers):
        boots = [nodes[0].visible_address] if nodes else []
        nodes.append(DHT(initial_peers=boots,
                         identity=Identity.generate(), rpc_timeout=2.0))
    modes = {}
    all_rows = []
    try:
        for epoch, (mode, pipelined) in enumerate(
                [("sequential", False), ("pipelined", True)]):
            t0 = time.monotonic()
            row, rows = run_mode(nodes, mode, pipelined, grads,
                                 args.budget_s, args.depth, epoch,
                                 args.allreduce_timeout,
                                 args.tick_elems)
            modes[mode] = row
            if pipelined:
                all_rows = rows  # the timeline the proof must come from
            print(f"{mode}: wall={row['round_wall_s']}s "
                  f"hidden={row['hidden_s']}s "
                  f"exposed={row['exposed_sync_s']}s "
                  f"complete={row['complete']} "
                  f"({time.monotonic() - t0:.0f}s incl. matchmaking)")
    finally:
        for nd in nodes:
            nd.shutdown()

    exp_seq = modes["sequential"]["exposed_sync_s"]
    exp_pip = modes["pipelined"]["exposed_sync_s"]
    reduction = 1.0 - (exp_pip / exp_seq) if exp_seq > 0 else 1.0
    proof = find_concurrent_hop(all_rows)
    result = {
        "metric": "exposed sync wall: collective wall not hidden "
                  "behind the trainer's bounded accumulate budget",
        "payload_mb": round(payload_mb, 1),
        "peers": args.peers,
        "wire": "u4+EF both legs, pinned",
        "budget_s": args.budget_s,
        "pipeline_depth": args.depth,
        "modes": modes,
        "exposed_reduction_frac": round(reduction, 4),
        "wall_reduction_frac": round(
            1.0 - modes["pipelined"]["round_wall_s"]
            / max(modes["sequential"]["round_wall_s"], 1e-9), 4),
        "concurrency_proof": None if proof is None else {
            "hop": {k: proof[0][k] for k in
                    ("peer", "phase", "t0", "dur_s")},
            "accumulate": {k: proof[1][k] for k in
                           ("peer", "phase", "t0", "dur_s")},
            "overlap_s": round(proof[2], 4),
        },
    }
    ok = (result["concurrency_proof"] is not None
          and modes["sequential"]["complete"]
          and modes["pipelined"]["complete"]
          and reduction >= 0.30)
    result["pass"] = ok
    print(f"exposed sync: {exp_seq}s -> {exp_pip}s "
          f"({reduction:.1%} reduction; gate >=30%), "
          f"concurrent hop span: "
          f"{'yes' if proof is not None else 'NO'}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"report: {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
