"""CPU rehearsal of the ``lfm2moe`` preset at a tiny size through
``harness.run_cell`` (test-only, as ``joyai_rehearse.py``):

    python tests/benchmark_tests/lfm2_rehearse.py <trace 0|1> <out dir>

The throw-away root is ``benchmark_rehearse.tiny_root`` with the preset,
its tiny overrides, the same as ``run_trainer`` flags, and the ``lfm2``
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

# a dense convolution layer, an attention layer and a convolution layer with
# experts; 64-wide heads, two a lane tile (the kernels'), four query heads
# over one key-value tile of two heads; half of the router's experts held
OVERRIDES = dict(
    hidden_size=128, num_hidden_layers=3,
    layer_kinds=("short_conv", "full_rope", "short_conv"), num_heads=4,
    num_kv_heads=2, expert_width=128, num_experts=8, experts_per_token=2,
    experts_held=4, expert_offset=2, vocab_size=96, text_seq_len=16,
    image_grid=4, vocab_text=48, vocab_image=48, dtype="float32",
    head_chunk=16, dense_width=128)


def trainer_args():
    return [x for key, value in OVERRIDES.items()
            for x in ("--" + key.replace("_", "-"),
                      *(value if isinstance(value, tuple) else (value,)))]


if __name__ == "__main__":
    trace, out = int(sys.argv[1]), Path(sys.argv[2])
    cell = tiny_root(out / "root", preset="lfm2moe", overrides=OVERRIDES,
                     trainer_args=trainer_args(), yardstick="lfm2")
    res = harness.run_cell(
        cell, seed=2**31 + 76543, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True)
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:6000])
