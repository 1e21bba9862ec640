"""Round-5 perf levers, measured (VERDICT r4 next #3).

Two levers PERF.md had left unmeasured:

(a) ``param_cast_hoist`` — hoist the f32->bf16 parameter casts out of the
    weight-shared scan so the shared-grad carry accumulates in bf16
    (halving the ~9% carry read-modify-write that survives scan_unroll=2)
    and the 4.1% of replayed casts disappear. Trajectory drift vs f32 is
    pinned by tests/test_train.py::test_param_cast_hoist_matches_baseline
    (25-step convergence parity on the CPU suite).
(b) the remat-policy x microbatch grid — save_ctx/save_attn were measured
    in r3 only at the points that FIT pre-GEGLU; the fused GEGLU freed the
    FF residual memory, so the full policy x micro grid is now reachable.

Run on the TPU host:  python scripts/perf_grid.py [row ...]
Appends driver-readable JSON lines to PERF_GRID.json at the repo root.
"""

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dalle_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

from bench import _bench, _is_oom  # noqa: E402
from dalle_tpu.config import flagship_model_config  # noqa: E402

# row -> (model overrides, [(micro, accum) ladder, highest first])
ROWS = {
    # control: the shipped operating point (PERF.md r4: 11.311)
    "base": (dict(), [(4, 64)]),
    # lever (a) at the shipped point
    "hoist": (dict(param_cast_hoist=True), [(4, 64)]),
    # lever (a) x larger micro (the freed casts may move the memory wall)
    "hoist_m6": (dict(param_cast_hoist=True), [(6, 42)]),
    # lever (b): the policy x micro grid, post-GEGLU/LN kernels
    "ctx_m6": (dict(remat_policy="save_ctx", remat_skip_blocks=0),
               [(6, 42)]),
    "ctx_m8": (dict(remat_policy="save_ctx", remat_skip_blocks=0),
               [(8, 32), (6, 42)]),
    "ctx_m6_skip1": (dict(remat_policy="save_ctx"), [(6, 42)]),
    "attn_m4": (dict(remat_policy="save_attn"), [(4, 64)]),
    "attn_m6": (dict(remat_policy="save_attn", remat_skip_blocks=0),
                [(6, 42), (4, 64)]),
    # levers combined
    "hoist_ctx_m6": (dict(param_cast_hoist=True, remat_policy="save_ctx",
                          remat_skip_blocks=0), [(6, 42)]),
    # round-2 follow-ups after save_attn/micro4 won the first grid pass
    "hoist_attn_m4": (dict(param_cast_hoist=True,
                           remat_policy="save_attn"), [(4, 64)]),
    "attn_m4_skip0": (dict(remat_policy="save_attn",
                           remat_skip_blocks=0), [(4, 64)]),
    "attn_m4_skip2": (dict(remat_policy="save_attn",
                           remat_skip_blocks=2), [(4, 64)]),
    # round-3 follow-ups: the two cells adjacent to the shipped winner
    "hoist_attn_m6_skip1": (dict(param_cast_hoist=True,
                                 remat_policy="save_attn"), [(6, 42)]),
    "hoist_attn_m4_a128": (dict(param_cast_hoist=True,
                                remat_policy="save_attn"), [(4, 128)]),
}


def main():
    rows = sys.argv[1:] or list(ROWS)
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "PERF_GRID.json")
    for row in rows:
        overrides, ladder = ROWS[row]
        result = None
        for micro, accum in ladder:
            cfg = flagship_model_config(**overrides)
            t0 = time.time()
            try:
                ips = _bench(cfg, micro, accum, warmup=1, iters=3)
                result = {"metric": f"dalle-1.3b train ({row})",
                          "overrides": {k: str(v) for k, v
                                        in overrides.items()},
                          "micro": micro, "accum": accum,
                          "value": round(ips, 3),
                          "unit": "images/sec/chip",
                          "total_s": round(time.time() - t0, 1)}
                break
            except Exception as e:  # noqa: BLE001
                if not _is_oom(e):
                    traceback.print_exc(file=sys.stderr)
                    msg = (str(e).splitlines() or [repr(e)])[0]
                    result = {"metric": f"dalle-1.3b train ({row})",
                              "value": None, "unit": "images/sec/chip",
                              "note": "error: " + msg[:200]}
                    break
                msg = (str(e).splitlines() or [repr(e)])[0]
                print(f"# {row} micro {micro}: OOM-class, walking down "
                      f"({msg[:160]})", file=sys.stderr, flush=True)
        if result is None:
            result = {"metric": f"dalle-1.3b train ({row})",
                      "value": None, "unit": "images/sec/chip",
                      "note": "memory wall: no ladder rung fits"}
        print(json.dumps(result), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(result) + "\n")


if __name__ == "__main__":
    main()
