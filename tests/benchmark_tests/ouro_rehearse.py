"""CPU rehearsal of the ``ouro2b6`` preset at a tiny size through
``harness.run_cell`` (test-only, as ``qwen3next_rehearse.py``):

    python tests/benchmark_tests/ouro_rehearse.py <trace 0|1> <out dir>

The throw-away root is ``benchmark_rehearse.tiny_root`` with the preset,
its tiny overrides, the same as ``run_trainer`` flags, and the ``ouro``
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

# one layer run twice at the widths the kernels take (the gate's seconds are
# compiles): one head of 128 lanes over one key-value head, a hidden size
# and a feed-forward of one lane tile, a sequence of whole sublane tiles
OVERRIDES = dict(
    hidden_size=128, num_hidden_layers=1, num_dense_layers=1, num_heads=1,
    num_kv_heads=1, head_dim=128, dense_width=128, vocab_size=96,
    text_seq_len=48, image_grid=4, vocab_text=48, vocab_image=48,
    dtype="float32", head_chunk=16, total_ut_steps=2)


def trainer_args():
    return [x for key, value in OVERRIDES.items()
            for x in ("--" + key.replace("_", "-"), value)]


if __name__ == "__main__":
    trace, out = int(sys.argv[1]), Path(sys.argv[2])
    cell = tiny_root(out / "root", preset="ouro2b6", overrides=OVERRIDES,
                     trainer_args=trainer_args(), yardstick="ouro")
    res = harness.run_cell(
        cell, seed=2**31 + 67676, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True)
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:6000])
