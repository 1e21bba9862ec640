"""Grouped matrix products for an expert layer: rows sorted by expert,
each expert's rows multiplied by that expert's weights.

``x`` is (rows, K), ``w`` (experts, K, N). The rows of one expert are
contiguous and **start at a multiple of the row tile** (``TILE`` rows;
the layout is :func:`tile_plan`'s), so every tile belongs to one expert:
the grid is one step a tile, a scalar-prefetch table names the tile's
expert, and the weights' BlockSpec follows it — an expert's (K, N) block is
fetched once for its run of tiles and stays in VMEM. Tiles past the last
group are skipped (their output rows are left unwritten: callers mask
them). Padding is a tile's rounding an expert, no more; an expert with no
row still owns one (empty) tile, so that its weight gradient is written.

**A tile that holds no row moves nothing.** The inactive tiles are the
buffer's tail (about half of it under a router that favours no expert), and
every row-tile BlockSpec maps their grid steps to the block of the last
active tile (``Tiles.block``), as the weights' BlockSpec repeats an
expert's index over its run: an unchanged index is no DMA, in or out, and
the bodies' ``pl.when(active)`` leaves the resident block as the last
active step wrote it. No row of an inactive tile is read or written.

Three products: ``x @ w[e]`` (forward), ``dy @ w[e].T`` (the rows'
gradient: the same kernel contracting the weights' last axis) and, for the
weights' gradient, ``x[tiles of e].T @ dy[tiles of e]`` accumulated in f32
over an expert's run of tiles. MXU operands are in the operands' dtype;
accumulation is f32.

**The expert block on the tile** (``hidden = act(x W_gate) * (x W_up)``,
where both weight blocks and the tiles fit VMEM: :func:`block_why_not`).
What is done to a tile between two products is done while the tile is in
VMEM, with every rounding where the three products above and the XLA code
between them put it: :func:`gated_hidden` reads a tile of ``x`` once and
writes ``g``, ``u`` (rounded to the rows' dtype, then read) and ``act(g) *
u``; :func:`gated_hidden_grads` multiplies ``dy`` by ``W_down.T``, rounds,
and writes the two cotangents and the ``act(g) * u`` that ``W_down``'s
gradient contracts; :func:`rows_grad` sums ``dg W_gate.T + du W_up.T`` in
f32 and rounds once (two buffers, each rounded, then added and rounded
again, before). :func:`weights_grad` is the third product alone.

**An expert that is not gated** (``hidden = act(x W_up)``, ``act`` the
square of ReLU: two products a direction and one weight block a kernel) has
the same two tile kernels with one operand fewer, :func:`hidden` and
:func:`hidden_grads` (``du = 2 relu(u) (dy W_down.T)``); its rows' gradient
is :func:`grouped_matmul` with ``transpose_w``, nothing to sum. **A width
that is no whole number of lane tiles** (1 856 = 14.5 x 128) goes through
every kernel here as it is: each block spans its array's whole minor
dimension, which Mosaic lays out in whole tiles with the last one's upper
lanes masked, so no leaf and no buffer is padded by this code.

What each form costs on the v5e (my chip run, PR 50:
``scripts/grouped_probe.py``, seed 500001, 8 held experts, bf16, a router
that favours none; microseconds a call, in brackets the active tiles'
products at the MXU's peak). *Old* is every step fetching its own tile,
*unmoved* the same kernels with ``Tiles.block``, *block* the expert block
on the tile; a forward is three products with the activation, a backward
six with the cotangents and the sum of the two ``dxs``::

   experts (tiles, active) a product  forward              backward
   2048 x 1792 (72, 38)    519 -> 422 1723 -> 1483 -> 1275 4283 -> 3780 -> 2618
     lfm2moe               [363]      [1087]               [2175]
   2560 x 768 (104, 51)    431 -> 308 1422 -> 1076 -> 920  3525 -> 2849 -> 1896
     smallthinker21b       [261]      [782]                [1564]
   2048 x 1024 (40, 21)    196 -> 154 580 -> 503 -> 460    1425 -> 1250 -> 907
     trinitymini           [115]      [343]                [687]
   2048 x 768 (24, 10)     94 -> 67   266 -> 202 -> 199    608 -> 516 -> 395
     joyaiflash            [41]       [123; bytes 137]     [245; bytes 279]

One fit of the two larger shapes, call = active x a tile's product at peak /
A + inactive x c: A 0.85 and c 2.7 us before (1.1 x the 2.4 us its two
DMAs take at the HBM's peak: an inactive step cost what it moved), A 0.87
and c 0.16 us after (a grid step's own overhead). The block runs at 82-85%
of the MXU's peak on the tiles that hold rows; ``joyaiflash``'s ten tiles
are bound by the weights' bytes. With ReLU the block's results are the
three products' bit for bit on the chip; with SiLU ``act(g) * u`` and the
cotangents differ from XLA's in 20-23% of the elements by one 16-bit
rounding (relative L2 0.0029): XLA's fusion elides ``act(g)``'s rounding
before the multiply (excess precision), a kernel performs it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 256
_VMEM = 64 * 1024 * 1024


class Tiles(NamedTuple):
    """Which expert every row tile belongs to (int32, one entry a tile)."""
    expert: jax.Array     # the tile's expert (the last one's past the end)
    active: jax.Array     # 1 where the tile holds rows of a group
    first: jax.Array      # 1 on the first tile of an expert's run
    last: jax.Array       # 1 on the last
    block: jax.Array      # the tile itself, the last active one's past the end


def tile_plan(sizes: jax.Array, n_tiles: int, tile: int = TILE):
    """Lay ``sizes`` rows an expert out in whole tiles. Returns (the
    first row of every expert, :class:`Tiles`); an expert with no row
    takes one tile all the same."""
    tiles_of = jnp.maximum(-(-sizes // tile), 1)
    end = jnp.cumsum(tiles_of)
    start = (end - tiles_of) * tile
    t = jnp.arange(n_tiles)
    expert = jnp.sum(t[:, None] >= end[None, :], axis=1)
    active = t < end[-1]
    expert = jnp.minimum(expert, sizes.shape[0] - 1)
    other = lambda shifted, edge: jnp.where(edge, -1, shifted)
    before = other(jnp.roll(expert, 1), t == 0)
    after = other(jnp.roll(jnp.where(active, expert, -1), -1),
                  t == n_tiles - 1)
    as_int = lambda x: x.astype(jnp.int32)
    return start, Tiles(as_int(expert), as_int(active),
                        as_int(active & (before != expert)),
                        as_int(active & (after != expert)),
                        as_int(jnp.minimum(t, end[-1] - 1)))


def act(name: str, g: jax.Array) -> jax.Array:
    """The gate's activation (``cfg.hidden_act``), in g's dtype; ``relu2``
    is the square of ReLU, what stands between the two products of an
    expert that is not gated."""
    if name == "relu":
        return jax.nn.relu(g)
    if name == "relu2":
        r = jax.nn.relu(g)
        return r * r
    return jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype)


def act_cotangent(name: str, u: jax.Array, dhidden: jax.Array) -> jax.Array:
    """The cotangent of ``u`` in ``hidden = act(u)``, for the activations
    an ungated expert has (``relu2``: ``2 relu(u) dhidden``)."""
    if name != "relu2":
        raise ValueError(f"no ungated expert has the activation {name!r}")
    r = jax.nn.relu(u)
    return dhidden * (r + r)


def gate_cotangent(name: str, g: jax.Array, u: jax.Array,
                   dhidden: jax.Array) -> jax.Array:
    """The cotangent of ``g`` in ``hidden = act(g) * u``."""
    if name == "relu":
        # compared in f32: Mosaic has no 16-bit comparison on the v5e
        return jnp.where(g.astype(jnp.float32) > 0, dhidden * u, 0)
    g32 = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(g32)
    return (dhidden * u * (sig * (1.0 + g32 * (1.0 - sig)))).astype(g.dtype)


def block_why_not(dim: int, width: int, dtype,
                  gated: bool = True) -> Optional[str]:
    """Why the expert block's kernels cannot take experts of ``dim`` x
    ``width`` in ``dtype``; None where an expert's two weight blocks (two
    buffers each), a grid step's tiles (two buffers each) and its f32
    intermediates fit VMEM. The largest of the three kernels decides. An
    expert that is not ``gated`` has one weight block a kernel
    (:func:`hidden`, :func:`hidden_grads`)."""
    size = jnp.dtype(dtype).itemsize
    weights, rows, hidden = dim * width * size, TILE * dim, TILE * width
    if not gated:
        need = max(
            # hidden: x; u, act(u); the product in f32 and a temporary
            2 * weights + 2 * size * (rows + 2 * hidden) + 2 * 4 * hidden,
            # hidden_grads: dy, u; du, act(u); dhidden and a temporary
            2 * weights + 2 * size * (rows + 3 * hidden) + 3 * 4 * hidden)
        if need > _VMEM:
            return (f"a block of {dim} x {width} and the tiles need "
                    f"{need / 2 ** 20:.1f} MiB of VMEM, over "
                    f"{_VMEM / 2 ** 20:g}")
        return None
    need = max(
        # gated_hidden: x; g, u, act(g) * u; both products in f32 and a
        # temporary of the activation's
        2 * 2 * weights + 2 * size * (rows + 3 * hidden) + 3 * 4 * hidden,
        # gated_hidden_grads: dy, g, u; dg, du, act(g) * u; dhidden and the
        # derivative's temporaries
        2 * weights + 2 * size * (rows + 5 * hidden) + 4 * 4 * hidden,
        # rows_grad: dg, du; dxs; the f32 sum
        2 * 2 * weights + 2 * size * (2 * hidden + rows) + 2 * 4 * rows)
    if need > _VMEM:
        return (f"two blocks of {dim} x {width} and the tiles need "
                f"{need / 2 ** 20:.1f} MiB of VMEM, over {_VMEM / 2 ** 20:g}")
    return None


def _dot(x, w, transpose_w: bool = False):
    dims = (((1,), (1 if transpose_w else 0,)), ((), ()))
    return jax.lax.dot_general(x, w, dims,
                               preferred_element_type=jnp.float32)


# Every kernel takes the five tables of ``Tiles`` first (scalar prefetch)
# and works only on a grid step whose tile holds rows.

def _gmm_kernel(expert_ref, active_ref, first_ref, last_ref, block_ref,
                x_ref, w_ref, o_ref, *, transpose_w: bool):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        o_ref[...] = _dot(x_ref[...], w_ref[0],
                          transpose_w).astype(o_ref.dtype)


def _tgmm_kernel(expert_ref, active_ref, first_ref, last_ref, block_ref,
                 x_ref, dy_ref, o_ref, acc):
    t = pl.program_id(0)

    @pl.when(first_ref[t] == 1)
    def _():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    @pl.when(active_ref[t] == 1)
    def _():
        acc[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last_ref[t] == 1)
    def _():
        o_ref[0] = acc[...].astype(o_ref.dtype)


def _gated_hidden_kernel(expert_ref, active_ref, first_ref, last_ref,
                         block_ref, x_ref, gate_ref, up_ref, g_ref, u_ref,
                         hidden_ref, *, name: str):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        x = x_ref[...]
        g = _dot(x, gate_ref[0]).astype(g_ref.dtype)
        u = _dot(x, up_ref[0]).astype(u_ref.dtype)
        g_ref[...] = g
        u_ref[...] = u
        hidden_ref[...] = act(name, g) * u


def _gated_hidden_grads_kernel(expert_ref, active_ref, first_ref, last_ref,
                               block_ref, dy_ref, down_ref, g_ref, u_ref,
                               dg_ref, du_ref, hidden_ref, *, name: str):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        g, u = g_ref[...], u_ref[...]
        dhidden = _dot(dy_ref[...], down_ref[0], True).astype(g.dtype)
        hidden = act(name, g)
        dg_ref[...] = gate_cotangent(name, g, u, dhidden)
        du_ref[...] = dhidden * hidden
        hidden_ref[...] = hidden * u


def _hidden_kernel(expert_ref, active_ref, first_ref, last_ref, block_ref,
                   x_ref, up_ref, u_ref, hidden_ref, *, name: str):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        u = _dot(x_ref[...], up_ref[0]).astype(u_ref.dtype)
        u_ref[...] = u
        hidden_ref[...] = act(name, u)


def _hidden_grads_kernel(expert_ref, active_ref, first_ref, last_ref,
                         block_ref, dy_ref, down_ref, u_ref, du_ref,
                         hidden_ref, *, name: str):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        u = u_ref[...]
        dhidden = _dot(dy_ref[...], down_ref[0], True).astype(u.dtype)
        du_ref[...] = act_cotangent(name, u, dhidden)
        hidden_ref[...] = act(name, u)


def _rows_grad_kernel(expert_ref, active_ref, first_ref, last_ref,
                      block_ref, dg_ref, du_ref, gate_ref, up_ref, o_ref):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        o_ref[...] = (_dot(dg_ref[...], gate_ref[0], True)
                      + _dot(du_ref[...], up_ref[0], True)
                      ).astype(o_ref.dtype)


def _over_tiles(kernel, tiles: Tiles, operands, out_shape, *, tile: int,
                interpret: bool, scratch=()):
    """``kernel`` on a grid of one step a row tile. Of ``operands`` and
    ``out_shape`` a (rows, width) array goes a row tile a step, an
    (experts, K, N) stack its tile's expert's block; a step past the last
    active tile names the blocks of the step before it."""
    def spec(like):
        if len(like.shape) == 2:
            return pl.BlockSpec((tile, like.shape[1]),
                                lambda t, e, a, f, la, b: (b[t], 0))
        return pl.BlockSpec((1,) + tuple(like.shape[1:]),
                            lambda t, e, a, f, la, b: (e[t], 0, 0))

    rows = operands[0].shape[0]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tiles), grid=(rows // tile,),
            in_specs=[spec(a) for a in operands],
            out_specs=jax.tree.map(spec, out_shape),
            scratch_shapes=list(scratch)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(*tiles, *operands)


def grouped_matmul(x, w, tiles: Tiles, tile: int = TILE,
                   interpret: bool = False, transpose_w: bool = False):
    """(rows, N): row tile ``t`` of ``x`` times ``w[tiles.expert[t]]``, or
    with ``transpose_w`` times its transpose (``w``: (experts, N, K)). Rows
    of inactive tiles are not written."""
    n = w.shape[1] if transpose_w else w.shape[2]
    return _over_tiles(
        functools.partial(_gmm_kernel, transpose_w=transpose_w), tiles,
        (x, w), jax.ShapeDtypeStruct((x.shape[0], n), x.dtype), tile=tile,
        interpret=interpret)


def weights_grad(x, dy, tiles: Tiles, like, tile: int = TILE,
                 interpret: bool = False):
    """``like``'s shape and dtype (experts, K, N): every expert's
    ``x.T @ dy`` over its run of tiles."""
    return _over_tiles(
        _tgmm_kernel, tiles, (x, dy),
        jax.ShapeDtypeStruct(like.shape, like.dtype), tile=tile,
        interpret=interpret,
        scratch=[pltpu.VMEM(like.shape[1:], jnp.float32)])


def grouped_matmul_grads(x, w, dy, tiles: Tiles, tile: int = TILE,
                         interpret: bool = False):
    """The cotangents (dx, dw) of :func:`grouped_matmul` for ``dy``."""
    return (grouped_matmul(dy, w, tiles, tile, interpret, transpose_w=True),
            weights_grad(x, dy, tiles, w, tile, interpret))


def gated_hidden(x, gate, up, tiles: Tiles, name: str, tile: int = TILE,
                 interpret: bool = False):
    """``(g, u, act(g) * u)``, each (rows, F): ``g`` and ``u`` the grouped
    products of ``x`` with ``gate`` and ``up``, rounded to ``x``'s dtype
    before the activation ``name`` reads them."""
    like = jax.ShapeDtypeStruct((x.shape[0], gate.shape[2]), x.dtype)
    return _over_tiles(
        functools.partial(_gated_hidden_kernel, name=name), tiles,
        (x, gate, up), (like,) * 3, tile=tile, interpret=interpret)


def gated_hidden_grads(dy, down, g, u, tiles: Tiles, name: str,
                       tile: int = TILE, interpret: bool = False):
    """``(dg, du, act(g) * u)`` for the cotangent ``dy`` (rows, D) of
    ``(act(g) * u) @ down``: the rows' gradient ``dy @ down.T`` is rounded
    to ``g``'s dtype and goes no further than the tile."""
    like = jax.ShapeDtypeStruct(g.shape, g.dtype)
    return _over_tiles(
        functools.partial(_gated_hidden_grads_kernel, name=name), tiles,
        (dy, down, g, u), (like,) * 3, tile=tile, interpret=interpret)


def hidden(x, up, tiles: Tiles, name: str, tile: int = TILE,
           interpret: bool = False):
    """``(u, act(u))``, each (rows, F), of an expert that is not gated:
    ``u`` the grouped product of ``x`` with ``up``, rounded to ``x``'s dtype
    before the activation ``name`` reads it."""
    like = jax.ShapeDtypeStruct((x.shape[0], up.shape[2]), x.dtype)
    return _over_tiles(
        functools.partial(_hidden_kernel, name=name), tiles, (x, up),
        (like,) * 2, tile=tile, interpret=interpret)


def hidden_grads(dy, down, u, tiles: Tiles, name: str, tile: int = TILE,
                 interpret: bool = False):
    """``(du, act(u))`` for the cotangent ``dy`` (rows, D) of ``act(u) @
    down``: the rows' gradient ``dy @ down.T`` is rounded to ``u``'s dtype
    and goes no further than the tile."""
    like = jax.ShapeDtypeStruct(u.shape, u.dtype)
    return _over_tiles(
        functools.partial(_hidden_grads_kernel, name=name), tiles,
        (dy, down, u), (like,) * 2, tile=tile, interpret=interpret)


def rows_grad(dg, du, gate, up, tiles: Tiles, tile: int = TILE,
              interpret: bool = False):
    """(rows, D): ``dg @ gate.T + du @ up.T`` an expert, summed in f32 and
    rounded once to ``dg``'s dtype."""
    return _over_tiles(
        _rows_grad_kernel, tiles, (dg, du, gate, up),
        jax.ShapeDtypeStruct((dg.shape[0], gate.shape[1]), dg.dtype),
        tile=tile, interpret=interpret)
