"""Single-pass RMS norm over each head's lanes, on the lanes layout.

``x`` is (B, T, H * head_dim): what a projection writes and the attention
kernels read, a head a run of ``head_dim`` lanes. The norm of queries and
keys (``qk_norm``) is over each head's own lanes with one (head_dim,)
scale for all heads. Written as a reshape to (B, T, H, head_dim) and a
reduction over the last axis it moves the heads from the lanes into the
second-minor axis, a physical relayout on the TPU, there and back,
forward, replayed and backward (PERF.md section 6, PR 34: 0.085 of the
0.155 s a step the norm cost ``trinitymini-train-solo`` were ``copy``).
Here a grid step owns a tile of one sample's rows by all lanes and every
head is a static, tile-aligned slice of it: the forward is one read and
one write, the backward one pass over ``x`` and ``dy`` that writes ``dx``
and a per-tile partial of ``dscale``. The operands are taken as they are:
no reshape is traced, so none lends a neighbouring fusion its name.

Numerics are ``models/sparse_lm.rms_norm``'s: statistics in f32 from the
input as it is (a lane reduction: on the v5e it keeps pace with the
tile's stream, and is faster than the sum as a product with a block of
ones on the MXU, whose f32 addends enter as two or three bf16 pieces;
PERF.md section 6, PR 34), ``eps`` inside the rsqrt, the scale applied in
f32, the result cast to ``x.dtype``. The backward recomputes the inverse
RMS from the tile it has loaded: residuals are {x, scale}.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_tpu.ops.pallas.geglu_kernels import _pick_block

LANES = 128
# numbers of one operand a grid step: 2 MiB of bf16. The backward holds
# three such operands twice (double-buffered) and f32 temporaries a head.
TILE = 1 << 20
# XLA names a Mosaic kernel after the innermost scope at the
# ``pallas_call``, which would be the jit around it: opened again inside,
# a trace reads ``qk_norm[mosaic]`` under its caller's ``attn/qk_norm``
SCOPE = "qk_norm"


def fits(tokens: int, width: int, head_dim: int) -> Optional[str]:
    """None where the kernels take samples of (tokens, width) with heads
    of ``head_dim`` lanes, else why not."""
    if head_dim % LANES:
        return f"head_dim {head_dim} is not whole {LANES}-lane tiles"
    if width % head_dim:
        return f"{width} lanes are not whole heads of {head_dim}"
    if tokens % 8:
        return f"{tokens} rows are not whole sublane tiles of 8"
    if 8 * width > TILE:
        return f"8 rows of {width} lanes pass a tile of {TILE} numbers"
    return None


def rows_tile(tokens: int, width: int) -> int:
    """Rows a grid step: the largest multiple of 8 that divides a sample's
    ``tokens`` and keeps the tile within ``TILE`` numbers."""
    return _pick_block(tokens, max(8, TILE // width))


def _inv_rms(x, eps):
    return jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _head_norm_fwd_kernel(x_ref, s_ref, out_ref, *, eps, head_dim):
    scale = s_ref[...].astype(jnp.float32)                   # (1, head_dim)
    for lo in range(0, x_ref.shape[1], head_dim):
        x = x_ref[:, lo:lo + head_dim].astype(jnp.float32)
        y = x * _inv_rms(x, eps)
        out_ref[:, lo:lo + head_dim] = (y * scale).astype(out_ref.dtype)


def _head_norm_bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, *, eps,
                          head_dim):
    scale = s_ref[...].astype(jnp.float32)
    for lo in range(0, x_ref.shape[1], head_dim):
        x = x_ref[:, lo:lo + head_dim].astype(jnp.float32)
        dy = dy_ref[:, lo:lo + head_dim].astype(jnp.float32)
        r = _inv_rms(x, eps)
        xhat = x * r
        g = dy * scale
        c = jnp.mean(g * xhat, axis=-1, keepdims=True)
        dx_ref[:, lo:lo + head_dim] = (r * (g - xhat * c)).astype(
            dx_ref.dtype)
        # the tile's rows summed sublane by sublane (adds of whole vregs):
        # the caller sums the slab's 8 rows with the tiles and the heads
        ds_ref[:, lo:lo + head_dim] = jnp.sum(
            (dy * xhat).reshape(-1, 8, head_dim), axis=0)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                               vmem_limit_bytes=64 * 1024 * 1024)


def _scale_spec(head_dim):
    return pl.BlockSpec((1, head_dim), lambda n, i: (0, 0))


@functools.partial(jax.jit,
                   static_argnames=("eps", "head_dim", "interpret"))
def _fwd_call(x, scale, *, eps, head_dim, interpret):
    b, t, width = x.shape
    bm = rows_tile(t, width)
    tile = pl.BlockSpec((None, bm, width), lambda n, i: (n, i, 0))
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_head_norm_fwd_kernel, eps=eps,
                              head_dim=head_dim),
            grid=(b, t // bm),
            in_specs=[tile, _scale_spec(head_dim)],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, scale[None])


@functools.partial(jax.jit,
                   static_argnames=("eps", "head_dim", "interpret"))
def _bwd_call(x, scale, dy, *, eps, head_dim, interpret):
    b, t, width = x.shape
    bm = rows_tile(t, width)
    tiles = t // bm
    tile = pl.BlockSpec((None, bm, width), lambda n, i: (n, i, 0))
    with jax.named_scope(SCOPE):
        dx, ds = pl.pallas_call(
            functools.partial(_head_norm_bwd_kernel, eps=eps,
                              head_dim=head_dim),
            grid=(b, tiles),
            in_specs=[tile, _scale_spec(head_dim), tile],
            out_specs=[tile, pl.BlockSpec((None, 8, width),
                                          lambda n, i: (n, i, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((b, tiles * 8, width),
                                            jnp.float32)],
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, scale[None], dy)
        return dx, jnp.sum(ds.reshape(-1, head_dim),
                           axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def head_rms_norm(x, scale, eps: float, head_dim: int,
                  interpret: bool = False):
    """``x`` (B, T, H * head_dim) normed over each head's ``head_dim``
    lanes, times ``scale`` (head_dim,), in ``x.dtype``; where
    :func:`fits`. Gradient residuals: {x, scale}."""
    return _fwd_call(x, scale, eps=eps, head_dim=head_dim,
                     interpret=interpret)


def _vjp_fwd(x, scale, eps, head_dim, interpret):
    return _fwd_call(x, scale, eps=eps, head_dim=head_dim,
                     interpret=interpret), (x, scale)


def _vjp_bwd(eps, head_dim, interpret, res, dy):
    x, scale = res
    return _bwd_call(x, scale, dy, eps=eps, head_dim=head_dim,
                     interpret=interpret)


head_rms_norm.defvjp(_vjp_fwd, _vjp_bwd)
