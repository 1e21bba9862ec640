"""CPU rehearsal of the ``qwen3next80b`` preset at a tiny size through
``harness.run_cell`` (test-only, as ``lfm2_rehearse.py``):

    python tests/benchmark_tests/qwen3next_rehearse.py <trace 0|1> <out dir>

The throw-away root is ``benchmark_rehearse.tiny_root`` with the preset,
its tiny overrides, the same as ``run_trainer`` flags, and the
``qwen3next`` yardstick; the kernels run interpreted. Its last line starts
with ``REHEARSAL``: never a result.
"""
import json
import os
import sys
import time
from pathlib import Path

T0 = time.perf_counter()

from benchmark_rehearse import tiny_root  # noqa: E402

from benchmark import harness  # noqa: E402

# one layer of each kind at the widths the kernels take (the gate's seconds
# are compiles): a gated-delta mixer of one query/key head of 128 serving two
# value heads of 128, an attention layer of two 256-wide query heads over one
# key-value head with 64 lanes rotated, an expert block in each; half of the
# router's experts held; a sequence of four chunks of whole sublane tiles
OVERRIDES = dict(
    hidden_size=128, num_hidden_layers=2,
    layer_kinds=("gated_delta", "full_rope"), num_heads=2, num_kv_heads=1,
    head_dim=256, expert_width=128, num_experts=8, experts_per_token=2,
    experts_held=4, expert_offset=2, vocab_size=96, text_seq_len=48,
    image_grid=4, vocab_text=48, vocab_image=48, dtype="float32",
    head_chunk=16, linear_num_key_heads=1, linear_num_value_heads=2,
    linear_key_head_dim=128, linear_value_head_dim=128, delta_chunk=16)


def trainer_args():
    return [x for key, value in OVERRIDES.items()
            for x in ("--" + key.replace("_", "-"),
                      *(value if isinstance(value, tuple) else (value,)))]


if __name__ == "__main__":
    trace, out = int(sys.argv[1]), Path(sys.argv[2])
    cell = tiny_root(out / "root", preset="qwen3next80b", overrides=OVERRIDES,
                     trainer_args=trainer_args(), yardstick="qwen3next")
    res = harness.run_cell(
        cell, seed=2**31 + 64646, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True)
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:6000])
