"""CPU rehearsal of the ``twotower30b`` preset at a tiny size through
``harness.run_cell`` (test-only, as ``lfm2_rehearse.py``):

    python tests/benchmark_tests/nemotronh_rehearse.py <trace 0|1> <out dir>

The throw-away root is ``benchmark_rehearse.tiny_root`` with the preset,
its tiny overrides, the same as ``run_trainer`` flags, and the
``nemotronh`` yardstick; the kernels run interpreted. Its last line starts
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

# a mixer, an expert layer and an attention layer, one part each; 128-wide
# heads, two query heads over one key-value tile; experts whose width ends
# in half a lane tile (as 1 856); half of the router's experts held; a
# sequence of four chunks
OVERRIDES = dict(
    hidden_size=128, num_hidden_layers=3,
    layer_kinds=("mamba2", "experts", "full_nope"), num_heads=2,
    num_kv_heads=1, head_dim=128, expert_width=192, shared_expert_width=128,
    num_experts=8, experts_per_token=2, experts_held=4, expert_offset=2,
    vocab_size=96, text_seq_len=16, image_grid=4, vocab_text=48,
    vocab_image=48, dtype="float32", head_chunk=16, mamba_num_heads=4,
    mamba_head_dim=8, ssm_groups=2, ssm_state_size=16, ssm_chunk=8)


def trainer_args():
    return [x for key, value in OVERRIDES.items()
            for x in ("--" + key.replace("_", "-"),
                      *(value if isinstance(value, tuple) else (value,)))]


if __name__ == "__main__":
    trace, out = int(sys.argv[1]), Path(sys.argv[2])
    cell = tiny_root(out / "root", preset="twotower30b", overrides=OVERRIDES,
                     trainer_args=trainer_args(), yardstick="nemotronh")
    res = harness.run_cell(
        cell, seed=2**31 + 57575, seconds=float(os.environ.get("SECS", "4")),
        trace=bool(trace), out_dir=out / "run", t_start=T0,
        require_backend=None, interpret_kernels=True)
    print("REHEARSAL (cpu, not a result):", json.dumps(res)[:6000])
