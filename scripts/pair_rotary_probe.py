#!/usr/bin/env python
"""The rotary of interleaved pairs at ``joyaiflash``'s cell's two shapes, on
the chip: the one pass on the lanes (``head_norm_kernels.pair_rotary``
through ``sparse_lm.pair_rotary``, reading ``q_b``'s and ``kv_a``'s
outputs where they lie) against the XLA expression it replaces
(``sparse_lm.rotary_interleaved_lanes`` on the slice).

Prints whether the forward is the expression's bit for bit and how far each
side's ``dx`` lies from the plain f32 gradient; exits 1 where a shape did
not take the pass or differs. No times: a call of 0.1 ms measures its
dispatch (PERF.md section 6, PR 42), and a traced benchmark run has the
kernel's (``rotary[mosaic]``). Fails without a TPU::

    python3 scripts/pair_rotary_probe.py [--seed N]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.config import joyaiflash_model_config
    from dalle_tpu.models import sparse_lm
    from dalle_tpu.ops.pallas import lowering
    from dalle_tpu.parallel.mesh import LANES_SPEC

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {device.platform}")
    cfg = joyaiflash_model_config()
    t, heads = cfg.total_seq_len, cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dtype = jnp.dtype(cfg.dtype)
    shapes = {"q_rope of q_b": ((1, t, heads * (nope + rope)), heads * nope),
              "k_rope of kv_a": ((1, t, cfg.kv_lora_rank + rope),
                                 cfg.kv_lora_rank)}
    f32 = lambda a: a.astype(jnp.float32)
    rel = lambda a, b: float(jnp.linalg.norm(f32(a) - f32(b))
                             / jnp.linalg.norm(f32(b)))
    out = {"device": device.device_kind, "seed": args.seed, "shapes": {}}
    for n, (name, (shape, start)) in enumerate(shapes.items()):
        width = shape[2] - start
        keys = jax.random.split(jax.random.PRNGKey(args.seed + n), 2)
        x = (jax.random.normal(keys[0], shape) * 2.0 + 0.3).astype(dtype)
        w = jax.random.normal(keys[1], (*shape[:2], width)).astype(dtype)
        now = functools.partial(sparse_lm.pair_rotary, start=start,
                                mesh=None, spec=LANES_SPEC, head_dim=rope,
                                theta=cfg.rope_theta)
        xla = lambda x: sparse_lm.rotary_interleaved_lanes(
            x[..., start:], rope, cfg.rope_theta)
        plain = lambda x: xla(f32(x))

        def grad(fn):
            return jax.jit(jax.grad(
                lambda x: jnp.sum(f32(fn(x)) * f32(w))))(x)

        y, ref = jax.jit(now)(x), jax.jit(xla)(x)
        why_not = lowering.why_not(sparse_lm.PAIR_ROTARY_SITE,
                                   sparse_lm._pair_key(t, width, rope))
        dx, dx_xla, dx_plain = grad(now), grad(xla), grad(plain)
        out["shapes"][name] = {
            "array": list(shape), "rotated_from_lane": start,
            "took_the_pass": why_not is None, "why_not": why_not,
            "forward_bit_for_bit": bool(
                y.dtype == ref.dtype and np.array_equal(y, ref)),
            "forward_elements_that_differ": int(jnp.sum(y != ref)),
            "dx_rel_to_plain_f32": {"pass": rel(dx, dx_plain),
                                    "xla": rel(dx_xla, dx_plain)},
            "dx_nought_before_the_lanes": not bool(
                jnp.any(dx[..., :start] != 0)),
        }
    print(json.dumps(out, indent=1))
    if not all(r["took_the_pass"] and r["forward_bit_for_bit"]
               for r in out["shapes"].values()):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
