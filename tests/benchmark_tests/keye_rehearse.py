"""CPU rehearsal of the ``keyevl2`` preset at a tiny size through
``harness.run_cell`` (test-only, as ``lfm2_rehearse.py``):

    python tests/benchmark_tests/keye_rehearse.py <trace 0|1> <out dir>

The throw-away root is ``benchmark_rehearse.tiny_root`` with the preset,
its tiny overrides, the same as ``run_trainer`` flags, and the ``keye``
yardstick; the kernels run interpreted. Its last line starts with
``REHEARSAL``: never a result.
"""
import json
import os
import sys
import time
from pathlib import Path

T0 = time.perf_counter()

from benchmark_rehearse import tiny_root  # noqa: E402

from benchmark import harness  # noqa: E402

# two layers; 128-wide heads (the kernels'), two query heads a key-value
# head; an indexer of two 64-wide heads that chooses 24 of up to 80 keys, so
# that the selection bites; a 4 x 4 image field after 64 text tokens, so
# that the three position rows differ; half of the router's experts held
OVERRIDES = dict(
    hidden_size=128, num_hidden_layers=2, num_heads=4, num_kv_heads=2,
    expert_width=128, num_experts=8, experts_per_token=2, experts_held=4,
    expert_offset=2, vocab_size=96, text_seq_len=64, image_grid=4,
    vocab_text=48, vocab_image=48, dtype="float32", head_chunk=16,
    index_topk=24, index_heads=2, index_chunk=32)


def trainer_args(overrides=OVERRIDES):
    return [x for key, value in overrides.items()
            for x in ("--" + key.replace("_", "-"), value)]


if __name__ == "__main__":
    trace, out = int(sys.argv[1]), Path(sys.argv[2])
    cell = tiny_root(out / "root", preset="keyevl2", overrides=OVERRIDES,
                     trainer_args=trainer_args(), yardstick="keye")
    res = harness.run_cell(
        cell, seed=2**31 + 52525, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True)
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:6000])
