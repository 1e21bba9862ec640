"""One pass over each head's lanes, on the lanes layout: the RMS norm of
queries and keys, their rotary, or both.

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

The rotary of a window layer's queries and keys is the same kind of work
on the same bytes, and as XLA fusions it ran at 3-6 times them
(``models/attention.apply_rotary_lanes`` with tables as wide as the array:
the cosine and sine of every element of (T, H * head_dim), two padded
shifted copies and a select for ``rotate_half``, forward, replayed and
backward; PERF.md section 6, PR 42). In the pass it reads ONE head's
(T, head_dim) f32 tables, a tile's rows of them a grid step, and
``rotate_half`` is one lane rotate by half a head (``pltpu.roll``; its own
inverse) times a sine that carries the sign (:func:`rotary_tables`):
``out = y cos + roll(y) sin``, transposed ``dy = dout cos + roll(dout
sin)``. :func:`per_head` does the norm where it is given a scale, the
rotary where it is given tables, both in one read and one write where
both (``trinitymini``'s window layers; ``smallthinker21b``'s have the
rotary alone, whose backward reads no ``x``). One kernel a direction for
all three: ``norm`` and ``rotary`` are static switches.

Numerics are ``models/sparse_lm.rms_norm``'s: statistics in f32 from the
input as it is (a lane reduction: on the v5e it keeps pace with the
tile's stream, and is faster than the sum as a product with a block of
ones on the MXU, whose f32 addends enter as two or three bf16 pieces;
PERF.md section 6, PR 34), ``eps`` inside the rsqrt, the scale applied in
f32, the result cast to ``x.dtype``; and ``apply_rotary_lanes``': f32
from f32 tables, the result cast to ``x.dtype``, with the normed value
rounded to ``x.dtype`` in between as the two passes had it, so the forward
is theirs bit for bit. The backward stays in f32 from ``dout`` to ``dx``
(the two passes rounded the cotangent between them) and recomputes the
inverse RMS from the tile it has loaded: residuals are {x, scale} of a
norm and the tables of a rotary.
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
# a trace reads ``qk_norm[mosaic]`` under its caller's ``attn/qk_norm``,
# and ``rotary[mosaic]`` under ``attn/rotary`` where the pass has no norm
SCOPE = "qk_norm"
ROTARY_SCOPE = "rotary"


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


def _head_norm_fwd_kernel(*refs, eps, head_dim, norm, rotary):
    """refs: x, [scale where ``norm``], [cos, sin where ``rotary``], out."""
    refs = iter(refs)
    x_ref = next(refs)
    scale = next(refs)[...].astype(jnp.float32) if norm else None
    cos, sin = (next(refs)[...], next(refs)[...]) if rotary else (None, None)
    out_ref = next(refs)
    for lo in range(0, x_ref.shape[1], head_dim):
        y = x_ref[:, lo:lo + head_dim].astype(jnp.float32)
        if norm:
            y = y * _inv_rms(y, eps) * scale
        if norm and rotary:
            # the rotary reads the normed value as the norm's own pass
            # would have written it
            y = y.astype(out_ref.dtype).astype(jnp.float32)
        if rotary:
            y = y * cos + pltpu.roll(y, head_dim // 2, 1) * sin
        out_ref[:, lo:lo + head_dim] = y.astype(out_ref.dtype)


def _head_norm_bwd_kernel(*refs, eps, head_dim, norm, rotary):
    """refs: [x, scale where ``norm``], dout, [cos, sin where ``rotary``],
    dx, [ds where ``norm``]."""
    refs = iter(refs)
    x_ref = next(refs) if norm else None
    scale = next(refs)[...].astype(jnp.float32) if norm else None
    dout_ref = next(refs)
    cos, sin = (next(refs)[...], next(refs)[...]) if rotary else (None, None)
    dx_ref = next(refs)
    ds_ref = next(refs) if norm else None
    for lo in range(0, dout_ref.shape[1], head_dim):
        dy = dout_ref[:, lo:lo + head_dim].astype(jnp.float32)
        if rotary:
            dy = dy * cos + pltpu.roll(dy * sin, head_dim // 2, 1)
        if norm:
            x = x_ref[:, lo:lo + head_dim].astype(jnp.float32)
            r = _inv_rms(x, eps)
            xhat = x * r
            g = dy * scale
            c = jnp.mean(g * xhat, axis=-1, keepdims=True)
            # the tile's rows summed sublane by sublane (adds of whole
            # vregs): the caller sums the slab's 8 rows with the tiles and
            # the heads
            ds_ref[:, lo:lo + head_dim] = jnp.sum(
                (dy * xhat).reshape(-1, 8, head_dim), axis=0)
            dy = r * (g - xhat * c)
        dx_ref[:, lo:lo + head_dim] = dy.astype(dx_ref.dtype)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                               vmem_limit_bytes=64 * 1024 * 1024)


def _specs(shape, head_dim):
    """(grid, a tile of ``x``, the scale, a tile's rows of a table, the
    ``dscale`` slab a tile writes). The sample is the inner grid axis: a
    table's block is then the same from one step to the next and is
    fetched once a row tile."""
    b, t, width = shape
    bm = rows_tile(t, width)
    return ((t // bm, b),
            pl.BlockSpec((None, bm, width), lambda i, n: (n, i, 0)),
            pl.BlockSpec((1, head_dim), lambda i, n: (0, 0)),
            pl.BlockSpec((bm, head_dim), lambda i, n: (i, 0)),
            pl.BlockSpec((None, 8, width), lambda i, n: (n, i, 0)))


def scope(norm: bool) -> str:
    """The name a trace gives the pass: the caller opens it too."""
    return SCOPE if norm else ROTARY_SCOPE


@functools.partial(jax.jit,
                   static_argnames=("eps", "head_dim", "interpret"))
def _fwd_call(x, scale, tables, *, eps, head_dim, interpret):
    norm, rotary = scale is not None, tables is not None
    grid, tile, scale_spec, table_spec, _ = _specs(x.shape, head_dim)
    with jax.named_scope(scope(norm)):
        return pl.pallas_call(
            functools.partial(_head_norm_fwd_kernel, eps=eps,
                              head_dim=head_dim, norm=norm, rotary=rotary),
            grid=grid,
            in_specs=[tile] + [scale_spec] * norm + [table_spec] * 2 * rotary,
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, *([scale[None]] if norm else []), *(tables or ()))


@functools.partial(jax.jit,
                   static_argnames=("eps", "head_dim", "interpret"))
def _bwd_call(x, scale, tables, dout, *, eps, head_dim, interpret):
    """(dx, dscale); ``x`` and ``scale`` None where there is no norm, and
    ``dscale`` then too."""
    norm, rotary = scale is not None, tables is not None
    grid, tile, scale_spec, table_spec, slab = _specs(dout.shape, head_dim)
    dx = jax.ShapeDtypeStruct(dout.shape, dout.dtype)
    ds = jax.ShapeDtypeStruct(
        (dout.shape[0], grid[0] * 8, dout.shape[2]), jnp.float32)
    with jax.named_scope(scope(norm)):
        out = pl.pallas_call(
            functools.partial(_head_norm_bwd_kernel, eps=eps,
                              head_dim=head_dim, norm=norm, rotary=rotary),
            grid=grid,
            in_specs=([tile, scale_spec] * norm + [tile]
                      + [table_spec] * 2 * rotary),
            out_specs=[tile] + [slab] * norm,
            out_shape=[dx] + [ds] * norm,
            compiler_params=_PARAMS,
            interpret=interpret,
        )(*([x, scale[None]] if norm else []), dout, *(tables or ()))
        if not norm:
            return out[0], None
        return out[0], jnp.sum(out[1].reshape(-1, head_dim),
                               axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def per_head(x, scale, tables, eps: float, head_dim: int,
             interpret: bool = False):
    """``x`` (B, T, H * head_dim) in one pass, in ``x.dtype``; where
    :func:`fits`. Each head's ``head_dim`` lanes normed, times ``scale``
    (head_dim,), where that is not None; then rotated by ``tables``, what
    :func:`rotary_tables` gives for the T positions, where those are not
    None. Gradient residuals: {x, scale} of a norm, and the tables."""
    return _fwd_call(x, scale, tables, eps=eps, head_dim=head_dim,
                     interpret=interpret)


def _vjp_fwd(x, scale, tables, eps, head_dim, interpret):
    out = _fwd_call(x, scale, tables, eps=eps, head_dim=head_dim,
                    interpret=interpret)
    return out, (x if scale is not None else None, scale, tables)


def _vjp_bwd(eps, head_dim, interpret, res, dout):
    x, scale, tables = res
    dx, dscale = _bwd_call(x, scale, tables, dout, eps=eps,
                           head_dim=head_dim, interpret=interpret)
    return dx, dscale, None


per_head.defvjp(_vjp_fwd, _vjp_bwd)


def head_rms_norm(x, scale, eps: float, head_dim: int,
                  interpret: bool = False):
    """:func:`per_head`'s norm alone."""
    return per_head(x, scale, None, eps, head_dim, interpret)


def rotary_tables(cos, sin):
    """What the pass reads of one head's (T, head_dim) ``cos`` and ``sin``
    (``models/attention.rotary_cos_sin``): ``sin`` with the sign of
    ``rotate_half`` in it, minus on a head's first half, so that the
    kernel's rotate-half is one lane rotate by half a head, which is its
    own inverse."""
    half = cos.shape[-1] // 2
    return cos, jnp.where(jnp.arange(2 * half) < half, -sin, sin)
