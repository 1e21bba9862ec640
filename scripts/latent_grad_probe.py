#!/usr/bin/env python
"""Latent attention's values and cotangents at the cell's shapes, on the
chip, written to a file two trees' runs can be compared by.

    python3 scripts/latent_grad_probe.py --seed <n> --out <file.npz>
    (cd <other tree> && python3 <this file> --seed <n> --out <other.npz>)
    python3 scripts/latent_grad_probe.py --compare <file.npz> <other.npz>

One sample of 8 192 tokens, 32 heads of 128 + 64 | 128 in bfloat16, the
operands laid out as ``q_b`` and ``kv_b`` write them. The tree before PR
46 takes the three slices and makes ``delta`` = rowsum(do * o) as XLA code;
this one reads the whole arrays and sums ``delta`` inside the backward
kernel: the values must agree bit for bit and the cotangents to the order
of an f32 sum over 128 lanes (PERF.md section 6, PR 46). ``--compare``
prints, an array, whether the two files agree bit for bit and the relative
L2 distance where not.
"""

from __future__ import annotations

import argparse
import inspect
import os
import sys

import numpy as np

sys.path.insert(0, os.getcwd())     # the tree it is run from the root of


def probe(seed: int, out: str) -> None:
    import jax
    import jax.numpy as jnp

    from dalle_tpu.ops.pallas import causal_attention_kernels as kernels

    tokens, heads = 8192, 32
    lanes = heads * 128
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 5)
    q, q_rope, kv, k_rope, w = (
        jax.random.normal(k, (1, tokens, width)).astype(jnp.bfloat16)
        for k, width in zip(keys, (heads * 192, heads * 64, heads * 256, 64,
                                   lanes)))
    whole = "kv" in inspect.signature(kernels.latent_attention).parameters

    def attend(q, q_rope, kv, k_rope):
        if whole:
            return kernels.latent_attention(q, q_rope, kv, k_rope)
        return kernels.latent_attention(q[..., :lanes], q_rope,
                                        kv[..., :lanes], k_rope,
                                        kv[..., lanes:])

    def both(*operands):
        value, vjp = jax.vjp(attend, *operands)
        return (value, *vjp(w))

    arrays = jax.jit(both)(q, q_rope, kv, k_rope)
    np.savez(out, **{name: np.asarray(a.astype(jnp.float32)) for name, a in
                     zip(("out", "dq", "dq_rope", "dkv", "dk_rope"), arrays)})
    print("in place" if whole else "sliced", jax.devices()[0].device_kind,
          "->", out)


def compare(a: str, b: str) -> None:
    a, b = np.load(a), np.load(b)
    for name in a.files:
        x, y = a[name].astype(np.float64), b[name].astype(np.float64)
        same = np.array_equal(x, y)
        print(f"{name}: bit for bit {same}, differing elements "
              f"{np.mean(x != y):.3e}, relative L2 "
              f"{np.linalg.norm(x - y) / np.linalg.norm(y):.3e}")


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out")
    parser.add_argument("--compare", nargs=2)
    args = parser.parse_args(argv)
    if args.compare:
        compare(*args.compare)
    else:
        probe(args.seed, args.out)


if __name__ == "__main__":
    main()
