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

Three products: ``x @ w[e]`` (forward), ``dy @ w[e].T`` (the rows'
gradient: the same kernel contracting the weights' last axis) and, for the
weights' gradient, ``x[tiles of e].T @ dy[tiles of e]`` accumulated in f32
over an expert's run of tiles. MXU operands are in the operands' dtype;
accumulation is f32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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
                        as_int(active & (after != expert)))


def _gmm_kernel(expert_ref, active_ref, x_ref, w_ref, o_ref, *,
                transpose_w: bool):
    @pl.when(active_ref[pl.program_id(0)] == 1)
    def _():
        dims = (((1,), (1 if transpose_w else 0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], dims,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _tgmm_kernel(expert_ref, active_ref, first_ref, last_ref, x_ref, dy_ref,
                 o_ref, acc):
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


def _gmm(x, w, tiles: Tiles, *, transpose_w: bool, tile: int,
         interpret: bool):
    rows, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile,),
            in_specs=[
                pl.BlockSpec((tile, k), lambda t, e, a: (t, 0)),
                pl.BlockSpec((1,) + w.shape[1:], lambda t, e, a: (e[t], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n), lambda t, e, a: (t, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(tiles.expert, tiles.active, x, w)


def _tgmm(x, dy, tiles: Tiles, experts: int, dtype, *, tile: int,
          interpret: bool):
    rows, k = x.shape
    n = dy.shape[1]
    row_tile = lambda width: pl.BlockSpec(
        (tile, width), lambda t, e, a, f, la: (t, 0))
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(rows // tile,),
            in_specs=[row_tile(k), row_tile(n)],
            out_specs=pl.BlockSpec((1, k, n),
                                   lambda t, e, a, f, la: (e[t], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((experts, k, n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(tiles.expert, tiles.active, tiles.first, tiles.last, x, dy)


def grouped_matmul(x, w, tiles: Tiles, tile: int = TILE,
                   interpret: bool = False):
    """(rows, N): row tile ``t`` of ``x`` times ``w[tiles.expert[t]]``.
    Rows of inactive tiles are not written."""
    return _gmm(x, w, tiles, transpose_w=False, tile=tile,
                interpret=interpret)


def grouped_matmul_grads(x, w, dy, tiles: Tiles, tile: int = TILE,
                         interpret: bool = False):
    """The cotangents (dx, dw) of :func:`grouped_matmul` for ``dy``."""
    return (_gmm(dy, w, tiles, transpose_w=True, tile=tile,
                 interpret=interpret),
            _tgmm(x, dy, tiles, w.shape[0], w.dtype, tile=tile,
                  interpret=interpret))
