"""One pass over each head's lanes, on the lanes layout: the RMS norm of
queries and keys, their rotary (``rotate_half``, or interleaved pairs),
or both.

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

Latent attention's rotary is of interleaved pairs ``(x_2i, x_2i+1)`` on
heads of 64 lanes, two a lane tile (``joyaiflash``): :func:`pair_rotary`,
a kernel of its own (the three configurations' programs do not move with
each other's) of the same shape: one lane tile's (T, 128) f32 tables, a
tile's rows a grid step with the sample the inner axis, the pair's other
member ``x[lane + 1]`` on the even lanes and ``x[lane - 1]`` on the odd
ones: one select of two lane rotates, by 127 and by 1, so the lane a
rotate wraps around is never the one chosen, and the pair's sign rides in
the sine (:func:`pair_tables`). The select is its own inverse, so one
kernel serves both directions: ``out = x cos + swap(x) sin``, transposed
``dx = dout cos + swap(dout sin)``. It reads its lanes where a wider
array holds them (the last 2 048 of ``q_b``'s (B, T, 6 144) output as
column block 2; the one key as the first half of the last lane tile of
``kv_a``'s (B, T, 576), a block that ends past the array: no slice is
traced, so XLA copies none in front of the kernel) and hands back one
``dx``; residuals are the tables. Numerics are
``models/sparse_lm.rotary_interleaved_lanes``': f32 from f32 tables made
by the same expression, the result cast to ``x.dtype``; on the v5e the
forward is that expression's bit for bit at the cell's two shapes
(``scripts/pair_rotary_probe.py``; PERF.md section 6, PR 45).
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
    return _rows_fit(tokens, width)


def _rows_fit(tokens: int, width: int) -> Optional[str]:
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


def _turn(y, tables, half: int, transpose: bool = False):
    """The rotary of a head's FIRST ``2 * half`` lanes alone, on the lane
    tiles ``y`` that hold them (:func:`partial_rotary_tables`): a lane of
    the first half takes its partner from ``half`` lanes on, one of the
    second from ``half`` lanes back, two lane rotates, each times a sine
    that is nought where the other's partner stands; ``transpose``: the
    cotangent's way back."""
    cos, up, down = tables
    back = y.shape[1] - half
    if transpose:
        return y * cos + pltpu.roll(y * up, back, 1) \
            + pltpu.roll(y * down, half, 1)
    return y * cos + pltpu.roll(y, half, 1) * up \
        + pltpu.roll(y, back, 1) * down


def _head_norm_fwd_kernel(*refs, eps, head_dim, norm, rotary, turned=0):
    """refs: x, [scale where ``norm``], [cos, sin where ``rotary``; cos,
    sin up, sin down where ``turned`` lanes of a head alone are], out."""
    refs = iter(refs)
    x_ref = next(refs)
    scale = next(refs)[...].astype(jnp.float32) if norm else None
    cos, sin = (next(refs)[...], next(refs)[...]) if rotary else (None, None)
    tables = (cos, sin, next(refs)[...]) if turned else None
    out_ref = next(refs)
    for lo in range(0, x_ref.shape[1], head_dim):
        y = x_ref[:, lo:lo + head_dim].astype(jnp.float32)
        if norm:
            y = y * _inv_rms(y, eps) * scale
        if norm and rotary:
            # the rotary reads the normed value as the norm's own pass
            # would have written it
            y = y.astype(out_ref.dtype).astype(jnp.float32)
        if turned:
            # the lane tiles that hold the turned lanes; the rest as it is
            wide = cos.shape[1]
            if wide < head_dim:
                out_ref[:, lo + wide:lo + head_dim] = y[:, wide:].astype(
                    out_ref.dtype)
            out_ref[:, lo:lo + wide] = _turn(
                y[:, :wide], tables, turned // 2).astype(out_ref.dtype)
            continue
        if rotary:
            y = y * cos + pltpu.roll(y, head_dim // 2, 1) * sin
        out_ref[:, lo:lo + head_dim] = y.astype(out_ref.dtype)


def _head_norm_bwd_kernel(*refs, eps, head_dim, norm, rotary, turned=0):
    """refs: [x, scale where ``norm``], dout, [cos, sin where ``rotary``;
    three tables where ``turned``], dx, [ds where ``norm``]."""
    refs = iter(refs)
    x_ref = next(refs) if norm else None
    scale = next(refs)[...].astype(jnp.float32) if norm else None
    dout_ref = next(refs)
    cos, sin = (next(refs)[...], next(refs)[...]) if rotary else (None, None)
    tables = (cos, sin, next(refs)[...]) if turned else None
    dx_ref = next(refs)
    ds_ref = next(refs) if norm else None
    for lo in range(0, dout_ref.shape[1], head_dim):
        dy = dout_ref[:, lo:lo + head_dim].astype(jnp.float32)
        if turned:
            wide = cos.shape[1]
            first = _turn(dy[:, :wide], tables, turned // 2, transpose=True)
            dy = first if wide == head_dim else jnp.concatenate(
                [first, dy[:, wide:]], axis=1)
        elif rotary:
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


def _specs(shape, head_dim, table: int = 0):
    """(grid, a tile of ``x``, the scale, a tile's rows of a table, the
    ``dscale`` slab a tile writes). The sample is the inner grid axis: a
    table's block is then the same from one step to the next and is
    fetched once a row tile. ``table``: a table's lanes, where they are
    not a head's."""
    b, t, width = shape
    bm = rows_tile(t, width)
    return ((t // bm, b),
            pl.BlockSpec((None, bm, width), lambda i, n: (n, i, 0)),
            pl.BlockSpec((1, head_dim), lambda i, n: (0, 0)),
            pl.BlockSpec((bm, table or head_dim), lambda i, n: (i, 0)),
            pl.BlockSpec((None, 8, width), lambda i, n: (n, i, 0)))


def scope(norm: bool) -> str:
    """The name a trace gives the pass: the caller opens it too."""
    return SCOPE if norm else ROTARY_SCOPE


def _turned(tables, turned: int):
    """The kernels' further keyword and a table's lanes where ``turned``
    lanes of a head alone are rotated (three tables); nothing else."""
    if not turned:
        return {}, 0
    return {"turned": turned}, tables[0].shape[1]


@functools.partial(jax.jit,
                   static_argnames=("eps", "head_dim", "interpret", "turned"))
def _fwd_call(x, scale, tables, *, eps, head_dim, interpret, turned=0):
    norm, rotary = scale is not None, tables is not None
    more, wide = _turned(tables, turned)
    grid, tile, scale_spec, table_spec, _ = _specs(x.shape, head_dim, wide)
    with jax.named_scope(scope(norm)):
        return pl.pallas_call(
            functools.partial(_head_norm_fwd_kernel, eps=eps,
                              head_dim=head_dim, norm=norm, rotary=rotary,
                              **more),
            grid=grid,
            in_specs=[tile] + [scale_spec] * norm
            + [table_spec] * len(tables or ()),
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, *([scale[None]] if norm else []), *(tables or ()))


@functools.partial(jax.jit,
                   static_argnames=("eps", "head_dim", "interpret", "turned"))
def _bwd_call(x, scale, tables, dout, *, eps, head_dim, interpret, turned=0):
    """(dx, dscale); ``x`` and ``scale`` None where there is no norm, and
    ``dscale`` then too."""
    norm, rotary = scale is not None, tables is not None
    more, wide = _turned(tables, turned)
    grid, tile, scale_spec, table_spec, slab = _specs(dout.shape, head_dim,
                                                      wide)
    dx = jax.ShapeDtypeStruct(dout.shape, dout.dtype)
    ds = jax.ShapeDtypeStruct(
        (dout.shape[0], grid[0] * 8, dout.shape[2]), jnp.float32)
    with jax.named_scope(scope(norm)):
        out = pl.pallas_call(
            functools.partial(_head_norm_bwd_kernel, eps=eps,
                              head_dim=head_dim, norm=norm, rotary=rotary,
                              **more),
            grid=grid,
            in_specs=([tile, scale_spec] * norm + [tile]
                      + [table_spec] * len(tables or ())),
            out_specs=[tile] + [slab] * norm,
            out_shape=[dx] + [ds] * norm,
            compiler_params=_PARAMS,
            interpret=interpret,
        )(*([x, scale[None]] if norm else []), dout, *(tables or ()))
        if not norm:
            return out[0], None
        return out[0], jnp.sum(out[1].reshape(-1, head_dim),
                               axis=0).astype(scale.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def per_head(x, scale, tables, eps: float, head_dim: int,
             interpret: bool = False, turned: int = 0):
    """``x`` (B, T, H * head_dim) in one pass, in ``x.dtype``; where
    :func:`fits`. Each head's ``head_dim`` lanes normed, times ``scale``
    (head_dim,), where that is not None; then rotated by ``tables``, what
    :func:`rotary_tables` gives for the T positions, where those are not
    None; with ``turned``, a head's first ``turned`` lanes alone, by what
    :func:`partial_rotary_tables` gives. Gradient residuals: {x, scale} of
    a norm, and the tables."""
    return _fwd_call(x, scale, tables, eps=eps, head_dim=head_dim,
                     interpret=interpret, turned=turned)


def _vjp_fwd(x, scale, tables, eps, head_dim, interpret, turned):
    out = _fwd_call(x, scale, tables, eps=eps, head_dim=head_dim,
                    interpret=interpret, turned=turned)
    return out, (x if scale is not None else None, scale, tables)


def _vjp_bwd(eps, head_dim, interpret, turned, res, dout):
    x, scale, tables = res
    dx, dscale = _bwd_call(x, scale, tables, dout, eps=eps,
                           head_dim=head_dim, interpret=interpret,
                           turned=turned)
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


def partial_rotary_tables(cos, sin):
    """What the pass reads where the rotary turns a head's first R lanes
    alone, of (T, R) ``cos`` and ``sin`` (``models/attention.rotary_cos_sin``
    of R): (T, W) ``cos``, ``sin up`` and ``sin down``, W the whole lane
    tiles that hold the R lanes; ``cos`` is 1 past them (those lanes pass
    as they are), ``sin up`` is the sine on the second half (its partner is
    R / 2 lanes back) and ``sin down`` minus the sine on the first (its
    partner R / 2 lanes on), noughts elsewhere (:func:`_turn`)."""
    turned = cos.shape[-1]
    pad = ((0, 0), (0, -turned % LANES))
    first = jnp.arange(turned) < turned // 2
    return (jnp.pad(cos, pad, constant_values=1.0),
            jnp.pad(jnp.where(first, 0.0, sin), pad),
            jnp.pad(jnp.where(first, -sin, 0.0), pad))


# ---------------------------------------------------------------------------
# The rotary of interleaved pairs (latent attention's 64-wide parts)
# ---------------------------------------------------------------------------

def pairs_fit(tokens: int, width: int, head_dim: int) -> Optional[str]:
    """None where :func:`pair_rotary` takes samples' (tokens, width) lanes
    of heads of ``head_dim`` lanes, else why not: whole heads side by side
    in a lane tile share one tile's tables, and an array narrower than a
    tile is one head (latent attention's shared key)."""
    if head_dim % 2 or LANES % head_dim:
        return (f"heads of {head_dim} lanes are not whole heads of pairs a "
                f"{LANES}-lane tile")
    if width % LANES and width != head_dim:
        return (f"{width} lanes are neither whole {LANES}-lane tiles nor "
                f"one head of {head_dim}")
    return _rows_fit(tokens, width)


def pair_tables(cos, sin):
    """What :func:`pair_rotary` reads of one lane tile's (T, 128) ``cos``
    and ``sin``, a pair's two lanes holding the same angle: ``sin`` with
    the pair's sign in it, minus on the even lanes (``out_2i = x_2i cos -
    x_2i+1 sin``), so that the kernel's other member of the pair is one
    select of two lane rotates, which is its own inverse."""
    return cos, jnp.where(jnp.arange(cos.shape[-1]) % 2 == 0, -sin, sin)


def pair_block(width: int) -> int:
    """Lanes of ``x`` a grid step of :func:`pair_rotary` reads for
    ``width`` rotated ones: the lanes before them in ``x`` have to be whole
    such blocks for the pass to read them where they lie."""
    return width if width % LANES == 0 else LANES


def pair_rows_tile(tokens: int, width: int) -> int:
    """Rows a grid step of :func:`pair_rotary`: an array narrower than a
    lane tile fills whole ones in VMEM."""
    return rows_tile(tokens, max(width, LANES))


def _pair_rotary_kernel(x_ref, cos_ref, sin_ref, out_ref, *, transpose):
    """``out = x cos + swap(x) sin`` a lane tile of the block, ``swap`` the
    pair's other member: ``x[lane + 1]`` on the even lanes, ``x[lane - 1]``
    on the odd ones, so the lane a rotate wraps around is never chosen.
    ``transpose``: ``dx = dout cos + swap(dout sin)``. An ``out`` narrower
    than a lane tile (the one key) is the first lanes of ``x``'s block and
    of the tables."""
    lanes = min(out_ref.shape[1], LANES)
    cos, sin = cos_ref[:, :lanes], sin_ref[:, :lanes]
    even = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1) % 2 == 0

    def swap(v):
        return jnp.where(even, pltpu.roll(v, lanes - 1, 1),
                         pltpu.roll(v, 1, 1))

    for lo in range(0, out_ref.shape[1], lanes):
        y = x_ref[:, lo:lo + lanes].astype(jnp.float32)
        y = y * cos + (swap(y * sin) if transpose else swap(y) * sin)
        out_ref[:, lo:lo + lanes] = y.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("before", "transpose", "interpret"))
def _pair_call(x, cos, sin, *, before, transpose, interpret):
    """``x``'s lanes from ``before`` on, rotated (or their cotangent): (B,
    T, width). A tile's rows of the tables a grid step with the sample the
    inner axis, as :func:`_specs` has them."""
    b, t, total = x.shape
    width = total - before
    block = pair_block(width) if before else width
    assert before % block == 0, (before, block)
    bm = pair_rows_tile(t, width)
    table = pl.BlockSpec((bm, LANES), lambda i, n: (i, 0))
    with jax.named_scope(ROTARY_SCOPE):
        return pl.pallas_call(
            functools.partial(_pair_rotary_kernel, transpose=transpose),
            grid=(t // bm, b),
            in_specs=[pl.BlockSpec((None, bm, block),
                                   lambda i, n: (n, i, before // block)),
                      table, table],
            out_specs=pl.BlockSpec((None, bm, width), lambda i, n: (n, i, 0)),
            out_shape=jax.ShapeDtypeStruct((b, t, width), x.dtype),
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def pair_rotary(x, tables, before: int = 0, interpret: bool = False):
    """The rotary of interleaved pairs ``(x_2i, x_2i+1)`` on the lanes of
    ``x`` (B, T, W) from ``before`` on, read where they lie (no slice is
    traced): where :func:`pairs_fit` takes them and ``before`` is whole
    :func:`pair_block` s. ``tables``: what :func:`pair_tables` gives for
    the T positions and one lane tile. Returns those lanes, (B, T, W -
    before), in ``x.dtype``. Gradient residuals: the tables."""
    return _pair_call(x, *tables, before=before, transpose=False,
                      interpret=interpret)


def _pair_vjp_fwd(x, tables, before, interpret):
    return _pair_call(x, *tables, before=before, transpose=False,
                      interpret=interpret), tables


def _pair_vjp_bwd(before, interpret, tables, dout):
    dx = _pair_call(dout, *tables, before=0, transpose=True,
                    interpret=interpret)
    # the lanes before are not this pass's: one pad of noughts, which XLA
    # fuses into the sum with what their own readers hand back
    return jnp.pad(dx, ((0, 0), (0, 0), (before, 0))), None


pair_rotary.defvjp(_pair_vjp_fwd, _pair_vjp_bwd)
