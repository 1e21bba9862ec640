"""Token-major sums over rows sorted by expert: every token's sum of
[weight x] the rows that computed its assignments, reading only rows that
hold one; and their transpose, every token to the rows of its assignments.

``rows`` is (R, D), laid out by ``grouped_matmul_kernels.tile_plan``: a
held expert's rows are contiguous. **Within an expert's group the rows
ascend strictly by token** (the plan sorts the flattened (token, slot)
assignments by expert with a stable sort, and a token names an expert at
most once), so the tokens of one tile of ``tokens`` consecutive tokens own
one contiguous *run* of rows in every group, never longer than the tile:
``start[i, e] .. start[i + 1, e]``. A token-major sum is then, a token
tile, one copy a held expert and a placement; no gather, no scatter.

The grid is one step a token tile. A step copies a window of ``WINDOW``
rows of every group (HBM -> VMEM, from the run's start aligned down to
``ALIGN`` rows) into one (held * WINDOW, D) buffer and places them with
0/1 matrices on the MXU: ``out = S @ buffer``, ``S[n, e * WINDOW + r] = 1``
where row r of expert e's window is token n's (``row_of[n, e]`` says
which). A run that passes its first window takes further rounds of
windows (at most ``tokens / WINDOW + 1``: :func:`spills` counts them).

**Nothing is rounded.** ``S`` is 0/1 and the buffer is in the rows' own
dtype, so a product with f32 accumulation *selects* a row. A routing
weight is f32 and the MXU's operands are not (Mosaic rounds an f32
operand to bf16 at default precision), so for 16-bit rows the weight goes
in as :func:`weight_pieces`: three bf16 numbers that add up to it
exactly, each in its own 0/piece matrix; a piece times a bf16 row is exact
in f32, and the sum of the three products is the f32 product to an f32
rounding. For f32 rows the one product runs at ``HIGHEST`` precision.
Only the order of a token's additions differs from a sum slot by slot.

**Rows that were never written** (the grouped products skip the tiles
past the last group) are zeroed in VMEM before the product, since 0 x NaN
is NaN: ``written`` is the first such row. Rows below it that hold no
assignment are finite (an active tile's product of zero rows) and meet a
zero column of ``S``.

**The transpose, tokens to rows** (:func:`rows_of`), walks the same runs
the other way: a step holds its tile of tokens in VMEM (read once, an
ordinary block) and places them into a window of every group with the same
0/1 matrices transposed, ``window = S.T @ tile``, then copies the windows
out (VMEM -> HBM). Runs of neighbouring tiles abut at rows that are no
multiple of ``ALIGN``, so a group's window is *carried* from step to step
in VMEM: it holds what its ``WINDOW`` rows of the buffer hold so far, moves
on by whole ``ALIGN`` rows as the runs advance, and is written again,
fuller, by every step. The last token tile's runs go on to their groups'
ends, so the rows of an active row tile that hold no assignment come out
zero; rows of inactive row tiles are not written.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TOKENS = 256      # tokens a grid step
WINDOW = 64       # rows of a run copied at a time
ALIGN = 16        # a copy starts at a whole sublane tile (bf16: 16 rows)
LANES = 128
_VMEM = 64 * 1024 * 1024
# of which the two buffers of windows may take (16 experts' at a width of
# 2 560 in bf16 compile within _VMEM, 32 experts' do not)
_BUFFERS = 12 * 1024 * 1024


def tokens_tile(n: int) -> int:
    """Tokens a grid step for ``n`` tokens: ``TOKENS``, or all of a
    smaller call's in whole sublanes."""
    return min(TOKENS, -(-n // 8) * 8)


def fits(held: int, dim: int, dtype) -> Optional[str]:
    """None where the kernel takes ``held`` experts' rows of ``dim``
    numbers of ``dtype``, else why not."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"rows in {dtype.name}"
    if held * WINDOW % LANES:
        return f"{held} windows of {WINDOW} rows are not whole lane tiles"
    if 2 * held * WINDOW * dim * dtype.itemsize > _BUFFERS:
        return (f"{held} windows of {WINDOW} x {dim} {dtype.name}, twice, "
                f"pass {_BUFFERS >> 20} MiB of VMEM")
    return None


def run_starts(count: jax.Array, first_row: jax.Array) -> jax.Array:
    """(tiles + 1, held) int32: the first row of every token tile's run in
    every group (the last line: the groups' ends). ``count``: (N, held)
    0/1, token n has an assignment to held expert e."""
    n, held = count.shape
    tokens = tokens_tile(n)
    count = jnp.pad(count.astype(jnp.int32), ((0, -n % tokens), (0, 0)))
    ends = jnp.cumsum(count.reshape(-1, tokens, held).sum(1), axis=0)
    return first_row[None, :] + jnp.concatenate(
        [jnp.zeros((1, held), jnp.int32), ends]).astype(jnp.int32)


def spills(start: jax.Array) -> jax.Array:
    """Runs that pass their first window (each costs its token tile a
    further round of copies and products)."""
    lo, hi = start[:-1], start[1:]
    return jnp.sum((hi > lo) & (hi - lo // ALIGN * ALIGN > WINDOW))


def weight_pieces(weight: jax.Array, dtype) -> jax.Array:
    """(N, pieces * held) f32: numbers of ``dtype``'s precision that add
    up to ``weight`` (N, held) exactly, piece-major: three for bf16 (8 of
    f32's 24 significant bits each), the weight itself for f32."""
    info = jnp.finfo(dtype)
    if info.bits == 32:
        return weight
    pieces, rest = [], weight
    for _ in range(-(-24 // (info.nmant + 1))):
        # not astype: XLA may drop a rounding that is converted back
        piece = jax.lax.reduce_precision(rest, info.nexp, info.nmant)
        pieces.append(piece)
        rest = rest - piece
    return jnp.concatenate(pieces, axis=1)


def _token_sum_kernel(start_ref, written_ref, row_ref, *refs, held: int,
                      pieces: int):
    if pieces:
        weight_ref, rows_ref, out_ref, buf, sem, acc = refs
    else:
        rows_ref, out_ref, buf, sem, acc = refs
    i, tiles = pl.program_id(0), pl.num_programs(0)
    slot = i % 2
    tn, dt = row_ref.shape[0], buf.dtype
    per = LANES // WINDOW               # experts a lane tile of S
    last = rows_ref.shape[0] - WINDOW
    written = written_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tn, LANES), 1)
    mine = [(lane >= s * WINDOW) & (lane < (s + 1) * WINDOW)
            for s in range(per)]
    precision = (jax.lax.Precision.HIGHEST if dt == jnp.float32
                 else jax.lax.Precision.DEFAULT)

    def windows(tile, q):
        """Where the q-th windows of ``tile``'s runs should start (the
        run's start aligned down, ``q`` windows on), and where they do:
        the buffer's last window starts inside it."""
        want = [(start_ref[tile * held + e] & -ALIGN) + q * WINDOW
                for e in range(held)]
        return want, [pl.multiple_of(jnp.minimum(w, last), ALIGN)
                      for w in want]

    def copies(at, slot):
        return [pltpu.make_async_copy(
            rows_ref.at[pl.ds(at[e], WINDOW), :],
            buf.at[slot, pl.ds(e * WINDOW, WINDOW), :], sem.at[slot, e])
            for e in range(held)]

    def product(want, at):
        """(tn, D) f32: what the windows in ``buf[slot]`` give the tile."""
        @pl.when(functools.reduce(jnp.maximum, at) + WINDOW > written)
        def _():
            for e in range(held):
                window = buf[slot, pl.ds(e * WINDOW, WINDOW), :]
                r = jax.lax.broadcasted_iota(jnp.int32, window.shape, 0)
                buf[slot, pl.ds(e * WINDOW, WINDOW), :] = jnp.where(
                    r + at[e] < written, window.astype(jnp.float32),
                    0.0).astype(dt)

        # a token's lane in its expert's window: the row's place in the
        # window, if this round's window is the one that owns the row
        # (a window held inside the buffer overlaps the one before it)
        match, weights = [], [[] for _ in range(pieces)]
        for g in range(held // per):
            target = jnp.full((tn, LANES), -1, jnp.int32)
            picked = [jnp.zeros((tn, LANES), jnp.float32)] * pieces
            for s in range(per):
                e = g * per + s
                row = row_ref[:, e:e + 1]
                place = jnp.where(row >= want[e], row - at[e] + s * WINDOW,
                                  -1)
                target = jnp.where(mine[s], place, target)
                picked = [jnp.where(mine[s], weight_ref[
                    :, p * held + e:p * held + e + 1], w)
                    for p, w in enumerate(picked)]
            match.append(target == lane)
            for p, w in enumerate(picked):
                weights[p].append(w)
        if not pieces:
            weights = [[jnp.ones((tn, LANES), jnp.float32)] * len(match)]
        data = buf[slot]
        total = None
        for piece in weights:
            s_matrix = jnp.concatenate(
                [jnp.where(m, w, 0.0).astype(dt)
                 for m, w in zip(match, piece)], axis=1)
            part = jax.lax.dot_general(
                s_matrix, data, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            total = part if total is None else total + part
        return total

    # the first windows of the next tile's runs are on their way while
    # this tile's are multiplied
    want, at = windows(i, 0)

    @pl.when(i == 0)
    def _():
        for copy in copies(at, slot):
            copy.start()

    @pl.when(i + 1 < tiles)
    def _():
        for copy in copies(windows(i + 1, 0)[1], 1 - slot):
            copy.start()

    # windows the longest of the tile's runs takes, the alignment's rows
    # before its start included
    rounds = functools.reduce(jnp.maximum, [
        jnp.where(hi > lo, jax.lax.div(hi - w + (WINDOW - 1), WINDOW), 1)
        for lo, hi, w in zip(
            (start_ref[i * held + e] for e in range(held)),
            (start_ref[(i + 1) * held + e] for e in range(held)), want)])

    def one_round(q, carry):
        want, at = windows(i, q)

        @pl.when(q > 0)
        def _():
            for copy in copies(at, slot):
                copy.start()

        for copy in copies(at, slot):
            copy.wait()
        part = product(want, at)

        @pl.when(q == 0)
        def _():
            acc[...] = part

        @pl.when(q > 0)
        def _():
            acc[...] += part

        return carry

    jax.lax.fori_loop(0, rounds, one_round, 0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def token_major_sum(rows, row_of, start, written, weight=None, *,
                    out_dtype=jnp.float32, interpret: bool = False):
    """(N, D) ``out_dtype``: token n's sum over the held experts e of
    [``weight[n, e]`` x] ``rows[row_of[n, e]]``, accumulated in f32.

    rows: (R, D). row_of: (N, held) int32, the row of token n's assignment
    to held expert e, -1 where it has none. start: (tiles + 1, held) from
    :func:`run_starts`. written:
    int32 scalar, rows from it on hold nothing and may hold anything.
    weight: (N, held) f32 or None."""
    n, held = row_of.shape
    tn = tokens_tile(n)
    tiles = -(-n // tn)
    pad = lambda x, fill: jnp.pad(x, ((0, tiles * tn - n), (0, 0)),
                                  constant_values=fill)
    operands = [pad(row_of, -1)]
    in_specs = [pl.BlockSpec((tn, held), lambda i, *_: (i, 0))]
    pieces = 0
    if weight is not None:
        split = weight_pieces(weight.astype(jnp.float32), rows.dtype)
        pieces = split.shape[1] // held
        operands.append(pad(split, 0.0))
        in_specs.append(pl.BlockSpec((tn, pieces * held),
                                     lambda i, *_: (i, 0)))
    d = rows.shape[1]
    return pl.pallas_call(
        functools.partial(_token_sum_kernel, held=held, pieces=pieces),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tn, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, held * WINDOW, d), rows.dtype),
                            pltpu.SemaphoreType.DMA((2, held)),
                            pltpu.VMEM((tn, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(start.reshape(-1).astype(jnp.int32),
      jnp.reshape(written, (1,)).astype(jnp.int32), *operands, rows)


# ---------------------------------------------------------------------------
# The transpose: tokens to rows
# ---------------------------------------------------------------------------

def _rows_of_kernel(start_ref, written_ref, row_ref, src_ref, out_ref, stage,
                    base, sem, *, held: int, tokens: int):
    """One token tile: its run of rows in every group, as ``S.T @ tile``
    added to the group's window of the buffer and written out whole.

    ``stage[e]`` is what rows ``base[e] .. base[e] + WINDOW`` of the buffer
    hold so far (rows no token has reached yet: zero), kept from step to
    step: runs of neighbouring tiles abut at rows that are no multiple of
    ``ALIGN``, and an aligned copy has to bring the neighbour's rows with
    it. A window moves on by whole ``ALIGN`` rows (the stage shifts), never
    past its group's last ``WINDOW`` rows, and every round writes every
    group's window again, so a copy per group is in flight while the next
    tile is placed and none overlaps another group's."""
    i, tiles = pl.program_id(0), pl.num_programs(0)
    tn, dt = src_ref.shape[0], stage.dtype
    rows = out_ref.shape[0]
    precision = (jax.lax.Precision.HIGHEST if dt == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    first = lambda e: start_ref[e]
    # a group ends where the next starts (whole row tiles), the last at
    # ``written``; its last window, inside the buffer
    ends = [first(e + 1) for e in range(held - 1)] + [written_ref[0]]
    last = [jnp.minimum(end, rows) - WINDOW for end in ends]
    lo = [start_ref[i * held + e] for e in range(held)]
    # the last tile's runs go on to their groups' ends: rows of active row
    # tiles that hold no assignment are zero
    hi = [jnp.where(i + 1 == tiles, ends[e], start_ref[(i + 1) * held + e])
          for e in range(held)]

    @pl.when(i == 0)
    def _():
        stage[...] = jnp.zeros(stage.shape, dt)
        for e in range(held):
            base[e] = first(e)

    def copies():
        return [pltpu.make_async_copy(
            stage.at[e, pl.ds(0, WINDOW), :],
            out_ref.at[pl.ds(pl.multiple_of(base[e], ALIGN), WINDOW), :],
            sem.at[e]) for e in range(held)]

    tile = src_ref[...]
    if tokens % tn:        # the last tile's rows past the tokens: anything
        n = jax.lax.broadcasted_iota(jnp.int32, (tn, 1), 0) + i * tn
        tile = jnp.where(n < tokens, tile, jnp.zeros_like(tile))
    r = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, tn), 0)

    def one_round(q, carry):
        # the first row this round places, and where the window then lies
        cur = [jnp.where(q == 0, lo[e], (lo[e] & -ALIGN) + q * WINDOW)
               for e in range(held)]
        at = [jnp.where(cur[e] < hi[e],
                        jnp.minimum(cur[e] & -ALIGN, last[e]), base[e])
              for e in range(held)]
        s_t = jnp.concatenate([
            (jnp.where(row_ref[e:e + 1, :] >= cur[e],
                       row_ref[e:e + 1, :] - at[e], -1) == r).astype(dt)
            for e in range(held)], axis=0)
        placed = jax.lax.dot_general(
            s_t, tile, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

        @pl.when((i > 0) | (q > 0))
        def _():
            for copy in copies():
                copy.wait()

        for e in range(held):
            shift = pl.multiple_of(at[e] - base[e], ALIGN)
            kept = stage[e, pl.ds(shift, WINDOW), :].astype(jnp.float32)
            stage[e, pl.ds(0, WINDOW), :] = (
                kept + placed[e * WINDOW:(e + 1) * WINDOW]).astype(dt)
            base[e] = at[e]
        for copy in copies():
            copy.start()
        return carry

    rounds = functools.reduce(jnp.maximum, [
        jnp.where(h > l, jax.lax.div(h - (l & -ALIGN) + (WINDOW - 1), WINDOW),
                  1) for l, h in zip(lo, hi)])
    jax.lax.fori_loop(0, rounds, one_round, 0)

    @pl.when(i + 1 == tiles)
    def _():
        for copy in copies():
            copy.wait()


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def rows_of(source, row_of, start, written, *, rows: int,
            interpret: bool = False):
    """(rows, D) in ``source``'s dtype, the transpose of
    :func:`token_major_sum` without weights: row ``row_of[n, e]`` is
    ``source[n]``, every other row below ``written`` is zero (a negative
    zero of ``source`` comes out positive: the one bit a selection by a
    product changes), and rows from ``written`` on are not written: they
    may hold anything.

    source: (N, D). row_of, start, written: as :func:`token_major_sum`
    takes them; ``rows`` a multiple of ``WINDOW``."""
    n, held = row_of.shape
    d = source.shape[1]
    tn = tokens_tile(n)
    tiles = -(-n // tn)
    row_t = jnp.pad(row_of.T, ((0, 0), (0, tiles * tn - n)),
                    constant_values=-1)
    return pl.pallas_call(
        functools.partial(_rows_of_kernel, held=held, tokens=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[pl.BlockSpec((held, tn), lambda i, *_: (0, i)),
                      pl.BlockSpec((tn, d), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((held, 2 * WINDOW, d), source.dtype),
                            pltpu.SMEM((held,), jnp.int32),
                            pltpu.SemaphoreType.DMA((held,))]),
        out_shape=jax.ShapeDtypeStruct((rows, d), source.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=_VMEM),
        interpret=interpret,
    )(start.reshape(-1).astype(jnp.int32),
      jnp.reshape(written, (1,)).astype(jnp.int32), row_t, source)
