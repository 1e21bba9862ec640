"""BASELINE.json configs 2-3 benchmark rows (VERDICT r3 next #5).

- config 2: DALL-E 1.3B DENSE — no weight sharing (shared_block_cycle=0,
  64 independent blocks, ~1.15B unique params). The interesting question
  is whether the full dense state (f32 params+grads ~9.2 GB + 8-bit
  moments ~2.3 GB) plus activations fits a 16 GB v5e at any microbatch.
- config 3: the dalle-pytorch attention-zoo variants — all-full
  (plain causal) and conv-heavy — against the shipped axial mix.

Appends driver-readable JSON lines to CONFIG_BENCH.json. Run on the TPU
host:  python scripts/config_bench.py [row ...]
rows: dense full conv axial (default: all)
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

ROWS = {
    # dense: no weight sharing. dense_scan stacks per-layer params under
    # ONE scanned attn-type group — the unrolled 64-block alternative is
    # an XLA program ~16x the shared model's, whose compile never
    # finished (>70 min before this row was restructured).
    # No partial remat (remat_skip needs a cycle); blanket remat +
    # streamed head are what make it fit at all.
    "dense": dict(shared_block_cycle=0, remat_skip_blocks=0,
                  scan_unroll=1, dense_scan=True),
    "full": dict(attn_types=("full", "full", "full", "full")),
    "conv": dict(attn_types=("conv_like", "axial_row", "conv_like",
                             "axial_row")),
    "axial": dict(),  # the shipped flagship mix (reference task.py:63-64)
}

#: (micro, accum) ladder per row — dense carries ~9x the optimizer/grad
#: state, so its ladder starts low
LADDERS = {
    "dense": [(2, 16), (1, 16), (1, 8)],
    "full": [(4, 32), (2, 16)],
    "conv": [(4, 32), (2, 16)],
    "axial": [(4, 32)],
}


def main():
    rows = sys.argv[1:] or list(ROWS)
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "CONFIG_BENCH.json")
    for row in rows:
        overrides = ROWS[row]
        result = None
        for micro, accum in LADDERS[row]:
            cfg = flagship_model_config(**overrides)
            t0 = time.time()
            try:
                ips = _bench(cfg, micro, accum, warmup=1, iters=3)
                result = {"metric": f"dalle-1.3b train ({row})",
                          "micro": micro, "accum": accum,
                          "value": round(ips, 3),
                          "unit": "images/sec/chip",
                          "total_s": round(time.time() - t0, 1)}
                break
            except Exception as e:  # noqa: BLE001
                if not _is_oom(e):
                    # record and move to the next ROW — one bad config
                    # must not cost the remaining rows their bench
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
