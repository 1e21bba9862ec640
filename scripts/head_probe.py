#!/usr/bin/env python
"""The sparse family's streamed head alone at a cell's micro-batch, on the
chip: the parent's checkpointed scan body (PR 60 and before), the rule
(``sparse_lm._streamed_nll``, since PR 61) and the variants PR 61 tried
(``dW``'s sum carried in f32, its term added in bfloat16, ``dlogits`` cast
or written once behind a barrier, ``dx`` rounded at once), each as the
gradient of a scaled total: device ms a call and the instructions that take
them, from a profile of three calls, and how far each variant's gradients
lie from the first's. Fails without a TPU::

    python3 scripts/head_probe.py [variant ...]
    HEAD_PROBE=rows,hidden,vocab,chunk python3 scripts/head_probe.py

(default 16384,2560,18992,2048: ``smallthinker21b``'s micro-batch of two
sequences; PERF.md section 6, PR 61, has the readings.)
"""
import collections
import functools
import os
import re
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dalle_tpu.models import sparse_lm

ROWS, D, V, CHUNK = (int(x) for x in os.environ.get(
    "HEAD_PROBE", "16384,2560,18992,2048").split(","))


DIMS = (((1,), (0,)), ((), ()))


def split(x):
    return x.reshape(-1, CHUNK, *x.shape[1:])


def logits_of(hc, kernel):
    with jax.named_scope("head"):
        return jax.lax.dot_general(hc, kernel, DIMS,
                                   preferred_element_type=jnp.float32)


def parent(h, kernel, targets, weights):
    """``_streamed_nll`` as it stood until PR 60: the backward pass replays
    the chunk's body."""
    @jax.checkpoint
    def body(sums, xs):
        hc, tc, wc = xs
        logits = logits_of(hc, kernel)
        with jax.named_scope("ce"):
            nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
                logits, tc[:, None], axis=-1)[:, 0]
            return sums + jnp.sum(nll[:, None] * wc, axis=0), None
    sums, _ = jax.lax.scan(body, jnp.zeros(weights.shape[1:], jnp.float32),
                           (split(h), split(targets), split(weights)))
    return jnp.sum(sums)


def variant(h, kernel, targets, weights, *, cast=False, bf16_add=False,
            dx_bf16=False, f32_carry=False, barrier=False):
    """The rule with its choices as flags (all off but ``barrier``: what
    ``sparse_lm`` ships)."""
    @jax.custom_vjp
    def scan(h, kernel, targets, weights):
        raise NotImplementedError("the probe differentiates")

    def forward(h, kernel, targets, weights):
        def body(carry, xs):
            (sums, dw), (hc, tc, wc) = carry, xs
            logits = logits_of(hc, kernel)
            with jax.named_scope("ce"):
                lse = jax.nn.logsumexp(logits, axis=-1)
                nll = lse - jnp.take_along_axis(
                    logits, tc[:, None], axis=-1)[:, 0]
                sums = sums + jnp.sum(nll[:, None] * wc, axis=0)
                hot = tc[:, None] == jax.lax.broadcasted_iota(
                    tc.dtype, logits.shape, 1)
                dlogits = (jnp.exp(logits - lse[:, None]) - hot) * jnp.sum(
                    wc, axis=1, keepdims=True)
                if cast:
                    dlogits = dlogits.astype(h.dtype)
                if barrier:
                    dlogits = jax.lax.optimization_barrier(dlogits)
            with jax.named_scope("head"):
                dx = jax.lax.dot_general(
                    dlogits, kernel, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if dx_bf16:
                    dx = dx.astype(h.dtype)
                term = jax.lax.dot_general(
                    hc, dlogits, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dw = (dw + term.astype(dw.dtype) if bf16_add
                      else (dw + term).astype(dw.dtype))
            return (sums, dw), dx
        dw0 = jnp.zeros(kernel.shape,
                        jnp.float32 if f32_carry else kernel.dtype)
        (sums, dw), dx = jax.lax.scan(
            body, (jnp.zeros(weights.shape[1:], jnp.float32), dw0),
            (split(h), split(targets), split(weights)))
        return jnp.sum(sums), (dx.reshape(-1, h.shape[1]), dw)

    def backward(made, c):
        dx, dw = made
        return ((dx * c).astype(h.dtype), (dw * c).astype(kernel.dtype),
                None, None)

    scan.defvjp(forward, backward)
    return scan(h, kernel, targets, weights)


VARIANTS = {
    "parent": parent,
    "rule": lambda *a: sparse_lm._streamed_nll(*a, CHUNK)[0],
    "no_barrier": variant,
    "bf16_add": functools.partial(variant, bf16_add=True),
    "cast_dlogits": functools.partial(variant, cast=True),
    "cast_and_dx_bf16": functools.partial(variant, cast=True, dx_bf16=True),
    "f32_carry": functools.partial(variant, f32_carry=True),
    "cast_barrier": functools.partial(variant, cast=True, barrier=True),
    "f32_barrier": functools.partial(variant, barrier=True),
}

CALLS = 3
CONTROL_FLOW = re.compile(r"%?(while|call|conditional)")


def device_ms(trace_dir: str):
    """``{instruction: [events, ms]}`` of the first device's leaf
    operations in the profile under ``trace_dir``."""
    path = next(os.path.join(root, name)
                for root, _, names in os.walk(trace_dir)
                for name in names if name.endswith(".xplane.pb"))
    acc = collections.defaultdict(lambda: [0, 0.0])
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                key = re.sub(r"\s+", " ", ev.name)[:170]
                if not CONTROL_FLOW.match(key):
                    acc[key][0] += 1
                    acc[key][1] += ev.duration_ns / 1e6
    return acc


def rel_l2(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main(names) -> None:
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(ROWS, D)), jnp.bfloat16)
    kernel = jnp.asarray(0.02 * rng.normal(size=(D, V)), jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, V, ROWS), jnp.int32)
    weights = jnp.asarray(rng.uniform(size=(ROWS, 2)), jnp.float32)
    first = None
    for name in names or list(VARIANTS):
        f = jax.jit(jax.value_and_grad(
            lambda h, k: VARIANTS[name](h, k, targets, weights) / ROWS,
            (0, 1)))
        out = jax.block_until_ready(f(h, kernel))
        with tempfile.TemporaryDirectory() as tmp:
            with jax.profiler.trace(tmp):
                for _ in range(CALLS):
                    out = jax.block_until_ready(f(h, kernel))
            ops = device_ms(tmp)
        first = first or out
        print("== %s: %.3f ms a call, loss %.6f, gradients from the first "
              "variant's: dx %.2e, dW %.2e" % (
                  name, sum(ms for _, ms in ops.values()) / CALLS,
                  float(out[0]), *map(rel_l2, out[1], first[1])))
        for key, (n, ms) in sorted(ops.items(),
                                   key=lambda kv: -kv[1][1])[:9]:
            print("   %7.3f ms x %3d a call  %s" % (ms / n, n // CALLS, key))


if __name__ == "__main__":
    main(sys.argv[1:])
