#!/usr/bin/env python
"""The blockwise attention kernels alone at the two 128-wide cells' shapes,
on the chip: what an edge tile's sub-tiles are worth, and at which size.

Times ``causal_attention``'s forward and its forward + backward (one
``vjp`` against a seeded cotangent: the forward kernel, ``delta`` and the
one-kernel backward) at B 1, T 8 192, bf16: 28 query heads over 4 with
windows None and 4 096 (``smallthinker21b``'s layers), 32 over 4 with 2 048
and None (``trinitymini``'s). The variants: the parent's module, handed as
a file (``--parent``, whole tiles of 512; and at ``block`` 256, the plain
control that needs no new code), and this tree's with ``SUB_BLOCK`` 512
(no sub-tiles: the tile's kind chooses the body and an interior tile is
not masked), 256 and 128. Says for each of this tree's variants whether
outputs and cotangents are the parent's bit for bit, and the largest
difference where not. Seconds are a host clock's around ``--calls`` calls of
10-60 ms each. Fails without a TPU (``--tiny``: a rehearsal on the CPU,
interpreted)::

    python3 scripts/band_probe.py --parent <tree>/dalle_tpu/ops/pallas/\\
        causal_attention_kernels.py [--out chiprun_out/<dir>]
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (query heads, key-value heads, window)
SHAPES = ((28, 4, None), (28, 4, 4096), (32, 4, 2048), (32, 4, None))
# this tree's variants, by ``SUB_BLOCK``
SUBS = (512, 256, 128)


def module_at(path: str):
    spec = importlib.util.spec_from_file_location("parent_kernels", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--parent", default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_tpu.ops.pallas import causal_attention_kernels as K
    from dalle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    tokens, shapes, dtype = 8192, SHAPES, jnp.bfloat16
    if args.tiny:
        tokens, shapes = 1536, ((2, 1, None), (2, 1, 1024))
    elif jax.default_backend() != "tpu":
        raise SystemExit("no TPU: a time from another backend is no reading")
    parent = module_at(args.parent) if args.parent else None

    def timed(fn, *operands):
        out = jax.block_until_ready(fn(*operands))      # compiles
        jax.block_until_ready(fn(*operands))
        start = time.perf_counter()
        for _ in range(args.calls):
            last = fn(*operands)
        jax.block_until_ready(last)
        return (time.perf_counter() - start) / args.calls, out

    def variant(module, window, block):
        """(forward s, forward + backward s, (out, dq, dk, dv))."""
        def attention(q, k, v):
            return module.causal_attention(q, k, v, window, block, args.tiny)

        def both(q, k, v, do):
            out, back = jax.vjp(attention, q, k, v)
            return (out, *back(do))
        forward_s, _ = timed(jax.jit(attention), q, k, v)
        both_s, arrays = timed(jax.jit(both), q, k, v, do)
        return forward_s, both_s, arrays

    rows = []
    for heads, kv_heads, window in shapes:
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        wide, narrow = heads * K.LANES, kv_heads * K.LANES
        q, k, v, do = (jax.random.normal(key, (1, tokens, n), dtype)
                       for key, n in zip(keys, (wide, narrow, narrow, wide)))
        line = {"heads": heads, "kv_heads": kv_heads, "window": window,
                "tokens": tokens}
        reference = None
        if parent is not None:
            fwd, both, reference = variant(parent, window, K.BLOCK)
            line["parent_512"] = {"fwd_s": fwd, "fwd_bwd_s": both}
            fwd, both, _ = variant(parent, window, K.BLOCK // 2)
            line["parent_block_256"] = {"fwd_s": fwd, "fwd_bwd_s": both}
        for sub in SUBS:
            K.SUB_BLOCK = sub               # read when the call is traced
            fwd, both, arrays = variant(K, window, K.BLOCK)
            said = {"fwd_s": fwd, "fwd_bwd_s": both,
                    "account": K.band_of(tokens, window)}
            if reference is not None:
                for name, got, want in zip(("out", "dq", "dk", "dv"), arrays,
                                           reference):
                    got, want = (np.asarray(x, np.float32)
                                 for x in (got, want))
                    said[name + "_bit_equal"] = bool(np.array_equal(got, want))
                    said[name + "_max_diff_over_max"] = float(
                        np.abs(got - want).max() / np.abs(want).max())
            line[f"sub_{sub}"] = said
        print(json.dumps(line), flush=True)
        rows.append(line)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        with open(os.path.join(args.out, "band_probe.json"), "w") as f:
            json.dump({"device": str(jax.devices()[0].device_kind),
                       "calls": args.calls, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
