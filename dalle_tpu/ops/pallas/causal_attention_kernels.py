"""Blockwise causal attention for long sequences, with grouped key-value
heads and an optional sliding window.

The zoo kernels (``attention_kernels.py``) hold a whole (sample, 128-lane
column) tile in VMEM, which ends near 1 280 tokens. These walk the score
matrix in (``block`` x ``block``) tiles, flash-attention style: scores and
probabilities live in VMEM only, forward and backward, so nothing of size
T x T is ever written to HBM; the backward recomputes a tile's
probabilities from q, k and the saved row statistics ``L = m + log(sum
exp(s - m))``.

**Only the tiles inside the band are visited.** Which (query block, key
block) pairs hold an allowed pair (key <= query, and with ``window``
query - key < window) is static, so the grid's last axis runs over a list
of exactly those pairs, handed to the kernel as scalar-prefetch tables (a
window layer of 4096 at T = 8192 visits 3/4 of a causal layer's tiles).
The forward walks the list query-block-major and keeps a query block's
accumulators in VMEM over its key blocks (2 MXU products a tile and head:
``q k^T``, ``p v``).

**An edge tile is multiplied by its sub-tiles that hold an allowed pair.**
A tile on the diagonal holds allowed pairs in its lower triangle only, one
on a window's far side in its upper triangle only: with whole tiles of 512
the band visits 1.11 to 1.18 times the allowed pairs of the cells' layers.
Which tiles are such *edge* tiles, and which (``SUB_BLOCK`` x ``SUB_BLOCK``)
sub-tiles of one hold no allowed pair, depends on ``d`` = query block - key
block, ``block``, ``window`` and the sub-tile's size alone
(:func:`edge_tiles`, :func:`band_pairs`' test applied to a sub-tile; a
window that is no multiple of the block has two kinds of edge tile on its
far side). The 128-wide kernels (``_causal_fwd_kernel``,
``_causal_bwd_kernel``, ``_causal_dq_kernel``, ``_causal_dkv_kernel``) tell a
tile's kind from the prefetched ``qi - ki`` (:func:`_by_kind`): an *interior*
tile runs its products whole and is not masked (every pair of it is
allowed); an edge tile runs them once a query sub-block, its rows against
the key rows of the sub-tiles it keeps, which lie side by side (3 of a
diagonal tile's 4 sub-tiles of 256: 256 x 256 and 256 x 512), masked, with
``dk`` / ``dv`` summed at those key rows. All slices are static and on row
boundaries. A masked pair's probability was an exact nought, so outputs and
``dq`` are the whole tile's bit for bit, and ``dk`` / ``dv`` (whose products
contract over the query rows, now in two parts) to f32 rounding.
:func:`band_account` counts what this leaves (1.05 and 1.09 times the
allowed pairs). :func:`sub_block` alone says who cuts and at what size:
:func:`causal_attention` hands its value to the kernels it picks, and
:func:`band_of` is the same call's account for the site's record. **The
other bodies still multiply whole tiles and mask them**: two 64-wide heads
a lane tile (``_halves_*_kernel``, whose ``sub`` is the tile), latent
attention (``_latent_*_kernel``), a selection read from data
(``_selected_*_kernel``) and the indexer's kernels (``indexer_kernels.py``),
which share ``band_pairs`` and ``_call`` and not ``_by_kind``.

**The backward computes a tile's probabilities once.** ``_causal_bwd_kernel``
walks the same query-block-major list and makes, a tile and head, the
probabilities and ``do v^T`` once and ``dq``, ``dk`` and ``dv`` from them:
5 products (``q k^T``, ``do v^T``, ``ds k``, ``p^T do``, ``ds^T q``), 2 + 5
= 7 with the forward's. ``dq`` is a query block's scratch, as the forward's
accumulators are; ``dk`` and ``dv`` are summed in (T, 128) f32 accumulators
that stay in VMEM for one key-value head's whole sequence (T KiB the pair).
Those grow with T, so :func:`fused_backward_fits`, a pure function of the
local shapes against the VMEM the call asks for, says where they no longer
fit; past that the backward is the two kernels it was before, ``dq``
query-block-major (3 products) and ``dk``/``dv`` key-block-major (4, the
scores and ``do v^T`` a second time: 2 + 7 = 9). Every accumulator sums in
the same order either way.

**Layout.** As the zoo kernels since PR 28, the projections' own
tokens-major arrays, one 128-wide head a lane tile (``head_dim`` 128; 64,
two a tile, further down):
``q`` (B, T, H*128), ``k``/``v`` (B, T, G*128) for G key-value heads. One
grid step takes a key-value head's (block, 128) tiles of ``k`` and ``v``
once and the ``H / G`` query heads that read them, side by side in the
(block, H/G * 128) tile of ``q``: a key-value tile is fetched once for its
whole group, and ``dk``/``dv`` are summed over the group in VMEM. The row
statistics are (B, G, T, 128) f32 with query head ``h`` of the group in
lane ``h``.

**Latent attention** (:func:`latent_attention`): a head's scores are the
sum of two products, ``q_nope k_nope^T`` over 128 lanes and ``q_rope
k_rope^T`` over 64, where ``k_rope`` is ONE (B, T, 64) head that every query
head reads, and its values are 128 wide: one key-value head a query head.
``_latent_fwd_kernel`` and ``_latent_bwd_kernel`` walk the same band with
the same tile arithmetic and statistics layout, ``LATENT_HEADS`` = 2 heads a
grid step, so that the two heads' 64-wide rotary parts are one 128-lane
tile of ``q_rope`` (B, T, H*64). **The kernels read ``q_nope``, ``k_nope``
and ``v`` where the projections wrote them:** ``q`` is ``q_b``'s whole
(B, T, H*192) output, every head's ``nope`` lanes first, and a step's two
heads' tile is its column block ``j`` of 256 lanes; ``kv`` is ``kv_b``'s
whole (B, T, H*256) output, every head's ``k_nope`` and then every head's
``v``: ``k_nope`` is column block ``j`` and ``v`` column block ``H/2 + j``
(kind "v_group", ``_call``'s ``v_block``), forward and backward alike, so no
slice stands between a projection and a kernel. Handed a pair ``(k_nope,
v)`` instead (a ``tp`` axis splits the heads of each part, not the lanes of
``kv``), both are read at block ``j``. The cotangents go back in the form
the operands came in: ``dq_nope`` padded with noughts to ``q``'s width,
``dk_nope`` and ``dv`` side by side as ``kv``'s, or the pair. The shared key
reaches the kernels as one
(B, T, 256) placement ``[k_rope, 0 | 0, k_rope]``: the step's (block, 128)
tile of ``q_rope`` times the first half's transpose is head 0's rotary
scores, times the second's head 1's, with no slice inside a lane tile; it is
written once a call, never once a head. ``dk_rope`` is summed over the key
blocks AND the heads in one (T, 128) f32 accumulator that stays in VMEM
over the whole of a sample's grid (the head axis is ``arbitrary`` there),
head 0's part in the lower 64 lanes and head 1's in the upper, which the
caller adds. The backward is the one kernel a tile: the same 5 products,
the three on the score side as a 128-wide and a 64-wide part each (8 MXU
calls; the rotary parts run 128 lanes deep for the 64 they need). **It
makes its own ``delta``:** the forward's output is one more operand, and at
a query block's first pair each head's rowsum(``do`` * ``o``) goes, in f32,
into a VMEM scratch the block's other pairs read (``do`` and ``o`` keep
their block over the run, so they are fetched once): no XLA reduce and no
(B, H/2, T, 128) array of it (:func:`_delta` stays ``causal_attention``'s).
Where its accumulators do not fit VMEM :func:`latent_fits` refuses the
shapes.

**Two 64-wide heads a lane tile** (``head_dim`` 64, :data:`HALF`): the same
arrays, grid, band walk, statistics layout and ``_call``; a grid step is a
key-value *tile*, two key-value heads side by side, and the ``H / G`` query
tiles (2 ``H / G`` query heads, local head ``h`` in statistics lane ``h``)
that read them. No array is 64 lanes wide: a head is told from its
neighbour by where the other operand of a product is nought. Once a grid
step the key and the value tile are *placed* (:func:`_placed_halves`: the
key-value head of local query head ``h``, half ``h // (H / G)`` of the
tile, moved by one lane rotate where it has to be into the half ``h % 2``
that the query head has in its own tile, noughts in the other half), so
``q_tile k_placed^T`` is one head's scores over 128 lanes of which 64 are
nought, ``p v_placed`` lands in the head's own half of the accumulator, and
``ds k_placed`` in its half of ``dq``. ``dk`` and ``dv`` are ``ds^T q`` and
``p^T do`` with the *other* head of the query tile noughted, summed apart
for the heads that sit in their key-value head's half and for those that
sit in the other, which one rotate a grid step brings home
(``_halves_*_kernel``; the MXU runs 128 deep and 128 wide for the 64 a head
needs, as in a 128-wide head's products at half the useful work).

Scores, softmax and statistics are f32; the MXU operands are in the
operands' dtype (bf16 in training).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HALF = LANES // 2                 # a 64-wide head: two a lane tile
WIDE = 2 * LANES                  # a 256-wide head: one over two lane tiles
BLOCK = 512
# the sub-tile the 128-wide kernels cut an edge tile into (:func:`edge_tiles`)
SUB_BLOCK = 256
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
# what every call asks of the compiler, and what the one-kernel backward's
# accumulators have to fit in (tests shrink it to reach the split kernels)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))       # a @ b.T


def blockwise_fits(q_width: int, kv_width: int, head_dim: int) -> Optional[str]:
    """None where the kernels take these local shapes, else why not."""
    if head_dim not in (LANES, HALF, WIDE):
        return (f"head_dim {head_dim} is neither one {LANES}-lane tile nor "
                "half of one")
    if q_width % kv_width or kv_width % max(LANES, head_dim):
        return f"{q_width} query lanes over {kv_width} key-value lanes"
    if q_width // kv_width * max(1, LANES // head_dim) > LANES:
        return "more query heads a group than statistics lanes"
    return None


def fused_backward_fits(tokens: int, group: int, itemsize: int,
                        block: int = BLOCK, selected: bool = False,
                        lanes: int = LANES) -> Optional[str]:
    """None where the one-kernel backward holds a key-value head's whole
    ``dk`` and ``dv`` in VMEM at these local shapes (``tokens`` a sample,
    ``group`` query heads a key-value head, operands of ``itemsize``
    bytes), else why not: then the backward is the ``dq`` and the
    ``dk``/``dv`` kernel, which hold a block each. With two 64-wide heads a
    lane tile the same sizes are a key-value tile's and its ``group`` query
    tiles'. ``selected``: the allowed pairs are data
    (:func:`selected_attention`), one more (block, block) f32 tile a step.
    ``lanes``: a head's, where it is wider than one lane tile."""
    t = tokens + -tokens % block
    tile = block * lanes
    need = (2 * t * lanes * 4                   # dk, dv accumulators, f32
            + 2 * 2 * t * lanes * itemsize      # their outputs, two buffers
            + group * tile * 4                  # dq's accumulator
            + 3 * 2 * group * tile * itemsize   # q, do, dq tiles
            + 2 * 2 * tile * itemsize           # k, v tiles
            + 2 * 2 * block * LANES * 4         # statistics, delta
            + 4 * block * block * 4             # scores, p, dp, ds
            + selected * 2 * block * block * 4)  # the selection's tile
    if need > VMEM_LIMIT_BYTES:
        return (f"dk and dv of {t} tokens need {need / 2 ** 20:.1f} MiB of "
                f"VMEM, over {VMEM_LIMIT_BYTES / 2 ** 20:g}")
    return None


def band_pairs(n_blocks: int, block: int, window: Optional[int],
               key_major: bool) -> np.ndarray:
    """The (query block, key block) pairs that hold an allowed (query,
    key) pair, as int32 rows ``[q, k, first, last]``: ``first``/``last``
    mark the ends of a run of pairs with the same major block."""
    pairs = [(i, j) for i in range(n_blocks) for j in range(i + 1)
             if window is None
             or (j + 1) * block - 1 >= i * block - (window - 1)]
    major = 1 if key_major else 0
    pairs.sort(key=lambda p: (p[major], p[1 - major]))
    rows = []
    for n, p in enumerate(pairs):
        first = n == 0 or pairs[n - 1][major] != p[major]
        last = n == len(pairs) - 1 or pairs[n + 1][major] != p[major]
        rows.append((p[0], p[1], int(first), int(last)))
    return np.asarray(rows, np.int32)


def sub_block(block: int, head_dim: int = LANES) -> int:
    """The sub-tile :func:`causal_attention` cuts an edge tile of ``block``
    into: :data:`SUB_BLOCK` for one head over whole lane tiles (128 wide, or
    256), where it divides the tile; else the tile itself (whole tiles)."""
    return (SUB_BLOCK if head_dim % LANES == 0 and block % SUB_BLOCK == 0
            else block)


def edge_tiles(block: int, window: Optional[int],
               sub: int) -> Dict[int, np.ndarray]:
    """The band's *edge* tiles, by ``d`` = query block - key block: those
    that hold an allowed pair and a pair that is not. ``{d: kept}``, ``kept``
    a (block / sub, block / sub) bool array: which (query sub-block, key
    sub-block) of the tile holds an allowed pair (:func:`band_pairs`' test,
    applied to a sub-tile). A tile of the band whose ``d`` is not listed is
    *interior*: every pair of it is allowed. ``d`` = 0, the diagonal, is
    always an edge; a window adds one or two at its far side."""
    at = np.arange(block // sub)
    edges = {}
    for d in range(1 if window is None else (window + block - 2) // block + 1):
        # the least and the largest query - key of each sub-tile
        least = d * block + (at[:, None] - at[None, :]) * sub - (sub - 1)
        largest = least + 2 * (sub - 1)
        kept, whole = largest >= 0, least >= 0
        if window is not None:
            kept, whole = kept & (least < window), whole & (largest < window)
        if not whole.all():
            edges[d] = kept
    return edges


def _strips(kept: np.ndarray, sub: int):
    """An edge tile's work by query sub-block: ``(rows, keys)`` slices of
    the tile, a sub-block's query rows and the key rows of the sub-tiles it
    keeps, which lie side by side (query - key falls along a row)."""
    for a, row in enumerate(kept):
        cols = np.flatnonzero(row)
        if len(cols):
            assert cols[-1] - cols[0] + 1 == len(cols), kept
            yield (slice(a * sub, (a + 1) * sub),
                   slice(cols[0] * sub, (cols[-1] + 1) * sub))


def band_account(n_blocks: int, block: int, window: Optional[int],
                 sub: int) -> Dict[str, int]:
    """What a call's band costs, in (query, key) pairs a head: its
    ``tiles`` and the ``edge_tiles`` among them, the pairs ``allowed``, the
    pairs of the whole tiles (``whole``) and the pairs ``visited`` with edge
    tiles cut into sub-tiles of ``sub`` (``sub`` = ``block``: ``whole``)."""
    table = band_pairs(n_blocks, block, window, key_major=False)
    edges = edge_tiles(block, window, sub)
    d = table[:, 0] - table[:, 1]
    dropped = sum(int((~edges[e]).sum()) * int((d == e).sum())
                  for e in edges) * sub * sub
    t, whole = n_blocks * block, len(table) * block * block
    reach = np.minimum(np.arange(t) + 1, window or t)
    return {"tiles": len(table),
            "edge_tiles": int(np.isin(d, list(edges)).sum()), "sub": sub,
            "allowed": int(reach.sum()), "whole": whole,
            "visited": whole - dropped}


def band_of(tokens: int, window: Optional[int], head_dim: int = LANES,
            block: int = BLOCK) -> Dict[str, int]:
    """:func:`band_account` of the call :func:`causal_attention` makes for
    ``tokens`` a sample: the padded length's blocks, and the sub-tile its
    kernels are handed."""
    return band_account(-(-tokens // block), block, window,
                        sub_block(block, head_dim))


def _within(first, shape, window: Optional[int]):
    """``shape`` (rows, keys) bool: which (query row, key column) may
    attend, ``first`` the query - key of the first row and column."""
    rel = first \
        + jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = rel >= 0
    return ok if window is None else ok & (rel < window)


def _allowed(qi, ki, block: int, window: Optional[int]):
    """(block, block) bool: which (query row, key column) of this tile may
    attend. One mask a grid step, shared by the group's heads."""
    return _within((qi - ki) * block, (block, block), window)


def _head(h: int, width: int = LANES) -> slice:
    """Head ``h``'s lanes of a group's tile, heads of ``width`` lanes."""
    return slice(h * width, (h + 1) * width)


def _tile_backward(q, do, k, v, allowed, lse, delta, scale):
    """One head's (block, block) tile in the backward: its probabilities
    from the saved statistics, and the scores' cotangent (times ``scale``,
    so that it is q's and k's), both f32. ``allowed``: None where every
    pair is."""
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    if allowed is not None:
        s = jnp.where(allowed, s, MASK_VALUE)
    prob = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return prob, prob * (dp - delta) * scale


def _softmax_step(h: int, s, v, m_s, l_s, acc_s, block: int, mine=None,
                  rows: slice = slice(None), width: int = LANES):
    """Head ``h``'s masked (block, block) scores of one key block folded
    into its running maximum, sum and accumulator. ``mine``: with two heads
    a lane tile, (block, 128) bool, the head's half of its tile ``h // 2``
    (``v`` is nought in the other half, whose accumulator stays). ``rows``:
    the query rows of the tile that ``s`` holds, over ``block`` keys."""
    m_prev, l_prev = m_s[h, rows], l_s[h, rows]      # (rows, 128)
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
    e = jnp.exp(s - jnp.tile(m_next, (1, block // LANES)))
    alpha = jnp.exp(m_prev - m_next)
    l_s[h, rows] = alpha * l_prev + jnp.sum(e, axis=1)[:, None]
    m_s[h, rows] = m_next
    lanes = _head(h, width)
    if mine is not None:
        lanes, alpha = _head(h // 2), jnp.where(mine, alpha, 1.0)
    if width > LANES:       # a head over several lane tiles: alpha on each
        alpha = jnp.tile(alpha, (1, width // LANES))
    acc_s[rows, lanes] = alpha * acc_s[rows, lanes] + jnp.dot(
        e.astype(v.dtype), v, preferred_element_type=jnp.float32)


def _write_forward(o_ref, stats_ref, m_s, l_s, acc_s, group: int,
                   block: int, width: int = LANES):
    """A query block's outputs and row statistics, head ``h`` in lane
    ``h``, after its last key block."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1)
    stats = jnp.zeros((block, LANES), jnp.float32)
    for h in range(group):
        l = l_s[h]
        sums = l if width == LANES else jnp.tile(l, (1, width // LANES))
        o_ref[0, :, _head(h, width)] = (
            acc_s[:, _head(h, width)] / sums).astype(o_ref.dtype)
        stats = jnp.where(lane == h, m_s[h] + jnp.log(l), stats)
    stats_ref[0, 0] = stats


def _by_kind(d, block: int, window: Optional[int], sub: int, work) -> None:
    """Run ``work(rows, keys, allowed)`` over the tile whose query block -
    key block is ``d``: once over an interior tile, whole and with no mask
    (``allowed`` None); over an edge tile once a query sub-block, its rows
    against the key rows :func:`edge_tiles` keeps for it and their mask.
    ``rows`` / ``keys``: static slices of the tile."""
    edges = edge_tiles(block, window, sub)
    whole = slice(0, block)

    @pl.when(functools.reduce(jnp.logical_and, [d != e for e in edges]))
    def _():
        work(whole, whole, None)

    for e, kept in edges.items():
        @pl.when(d == e)
        def _():
            for rows, keys in _strips(kept, sub):
                work(rows, keys, _within(
                    e * block + rows.start - keys.start,
                    (rows.stop - rows.start, keys.stop - keys.start), window))


def _causal_fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, o_ref, stats_ref, m_s, l_s, acc_s, *,
                       scale: float, group: int, block: int,
                       window: Optional[int], sub: int, width: int = LANES):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def fold(rows, keys, allowed):
        k, v = k_ref[0, keys], v_ref[0, keys]
        for h in range(group):
            s = jax.lax.dot_general(q_ref[0, rows, _head(h, width)], k, _NT,
                                    preferred_element_type=jnp.float32) * scale
            if allowed is not None:
                s = jnp.where(allowed, s, MASK_VALUE)
            _softmax_step(h, s, v, m_s, l_s, acc_s, k.shape[0], rows=rows,
                          width=width)

    _by_kind(qi_ref[p] - ki_ref[p], block, window, sub, fold)

    @pl.when(last_ref[p] == 1)
    def _():
        _write_forward(o_ref, stats_ref, m_s, l_s, acc_s, group, block,
                       width)


def _strip_backward(h: int, rows, keys, allowed, q_ref, k_ref, v_ref, do_ref,
                    stats_ref, delta_ref, scale, width: int = LANES):
    """:func:`_tile_backward` of head ``h`` over the tile's ``rows`` and
    ``keys``, with the operands it read: (q, do, k, prob, ds), ``ds`` in the
    operands' dtype."""
    lanes = _head(h, width)
    q, do = q_ref[0, rows, lanes], do_ref[0, rows, lanes]
    k = k_ref[0, keys]
    prob, ds = _tile_backward(q, do, k, v_ref[0, keys], allowed,
                              stats_ref[0, 0, rows, h:h + 1],
                              delta_ref[0, 0, rows, h:h + 1], scale)
    # cast to the operands' dtype once, and transpose the narrow copy
    return q, do, k, prob, ds.astype(k.dtype)


def _causal_dq_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                      v_ref, do_ref, stats_ref, delta_ref, dq_ref, dq_s, *,
                      scale: float, group: int, block: int,
                      window: Optional[int], sub: int, width: int = LANES):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    def fold(rows, keys, allowed):
        for h in range(group):
            _, _, k, _, ds = _strip_backward(
                h, rows, keys, allowed, q_ref, k_ref, v_ref, do_ref,
                stats_ref, delta_ref, scale, width)
            dq_s[rows, _head(h, width)] += jnp.dot(
                ds, k, preferred_element_type=jnp.float32)

    _by_kind(qi_ref[p] - ki_ref[p], block, window, sub, fold)

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _causal_dkv_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, do_ref, stats_ref, delta_ref, dk_ref, dv_ref,
                       dk_s, dv_s, *, scale: float, group: int, block: int,
                       window: Optional[int], sub: int, width: int = LANES):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def fold(rows, keys, allowed):
        for h in range(group):
            q, do, _, prob, ds = _strip_backward(
                h, rows, keys, allowed, q_ref, k_ref, v_ref, do_ref,
                stats_ref, delta_ref, scale, width)
            dv_s[keys, :] += jnp.dot(prob.astype(do.dtype).T, do,
                                     preferred_element_type=jnp.float32)
            dk_s[keys, :] += jnp.dot(ds.T, q,
                                     preferred_element_type=jnp.float32)

    _by_kind(qi_ref[p] - ki_ref[p], block, window, sub, fold)

    @pl.when(last_ref[p] == 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _causal_bwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, do_ref, stats_ref, delta_ref, dq_ref, dk_ref,
                       dv_ref, dq_s, dk_s, dv_s, *, scale: float, group: int,
                       block: int, window: Optional[int], sub: int,
                       width: int = LANES):
    """``dq``, ``dk`` and ``dv`` from one pass over the band, query-block-
    major: ``dq_s`` holds a query block over its key blocks, ``dk_s`` and
    ``dv_s`` (T, 128) a key-value head's whole sequence over all pairs."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    def fold(rows, keys, allowed):
        at = pl.ds(pl.multiple_of(ki_ref[p] * block + keys.start, sub),
                   keys.stop - keys.start)
        for h in range(group):
            q, do, k, prob, ds = _strip_backward(
                h, rows, keys, allowed, q_ref, k_ref, v_ref, do_ref,
                stats_ref, delta_ref, scale, width)
            dq_s[rows, _head(h, width)] += jnp.dot(
                ds, k, preferred_element_type=jnp.float32)
            dv_s[at, :] += jnp.dot(prob.astype(do.dtype).T, do,
                                   preferred_element_type=jnp.float32)
            dk_s[at, :] += jnp.dot(ds.T, q,
                                   preferred_element_type=jnp.float32)

    _by_kind(qi_ref[p] - ki_ref[p], block, window, sub, fold)

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Two 64-wide heads a lane tile (whole tiles: their ``sub`` is the tile,
# :func:`sub_block`)
# ---------------------------------------------------------------------------

def _lane_half(block: int):
    """(block, 128) int32: 0 on a tile's first 64 lanes, 1 on the rest."""
    return jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1) // HALF


def _placed_halves(x, half, group: int):
    """A key-value tile's two heads where a step's query heads need them:
    ``{(b, a): (block, 128)}``, head ``b`` of ``x`` in half ``a`` and
    noughts in the other, for the ``(h // group, h % 2)`` of the step's
    2 ``group`` query heads. One lane rotate at most, and a select each."""
    swapped = None
    placed = {}
    for h in range(2 * group):
        b, a = h // group, h % 2
        if (b, a) in placed:
            continue
        source = x
        if a != b:
            if swapped is None:
                # Mosaic rotates 32-bit lanes only: widened and back, exact
                swapped = pltpu.roll(x.astype(jnp.float32), HALF,
                                     1).astype(x.dtype)
            source = swapped
        placed[b, a] = jnp.where(half == a, source, jnp.zeros_like(x))
    return placed


def _halves_fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, o_ref, stats_ref, m_s, l_s, acc_s, *,
                       scale: float, group: int, block: int,
                       window: Optional[int], sub: int):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    half = _lane_half(block)
    ks = _placed_halves(k_ref[0], half, group)
    vs = _placed_halves(v_ref[0], half, group)
    for h in range(2 * group):
        at = h // group, h % 2
        s = jax.lax.dot_general(q_ref[0, :, _head(h // 2)], ks[at], _NT,
                                preferred_element_type=jnp.float32) * scale
        _softmax_step(h, jnp.where(allowed, s, MASK_VALUE), vs[at], m_s, l_s,
                      acc_s, block, mine=half == h % 2)

    @pl.when(last_ref[p] == 1)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1)
        stats = jnp.zeros((block, LANES), jnp.float32)
        for h in range(2 * group):
            stats = jnp.where(lane == h, m_s[h] + jnp.log(l_s[h]), stats)
        stats_ref[0, 0] = stats
        for c in range(group):
            l = jnp.where(half == 0, l_s[2 * c], l_s[2 * c + 1])
            o_ref[0, :, _head(c)] = (acc_s[:, _head(c)] / l).astype(
                o_ref.dtype)


def _halves_backward(h: int, q_ref, do_ref, ks, vs, allowed, half, stats,
                     delta, scale, group: int, own: bool):
    """Local query head ``h``'s tile in the backward: its placed key, its
    probabilities and scores' cotangent (``_tile_backward``) and its query
    and output cotangent, the tile's other head noughted where ``own`` (the
    products that sum over the queries, ``dk`` and ``dv``, read them)."""
    at, lanes = (h // group, h % 2), _head(h // 2)
    q, do = q_ref[0, :, lanes], do_ref[0, :, lanes]
    if own:
        mine = half == h % 2
        q = jnp.where(mine, q, jnp.zeros_like(q))
        do = jnp.where(mine, do, jnp.zeros_like(do))
    prob, ds = _tile_backward(q, do, ks[at], vs[at], allowed,
                              stats[:, h:h + 1], delta[:, h:h + 1], scale)
    return q, do, ks[at], prob, ds.astype(q.dtype)


def _home(parts):
    """``dk`` or ``dv`` of a key-value tile from its query heads' (block,
    128) parts, each in the half its query head has: those of a head that
    sits in its key-value head's half as they are, the others through one
    lane rotate. parts: [(crossed, part)]."""
    total = sum(part for crossed, part in parts if not crossed)
    crossed = [part for crossed, part in parts if crossed]
    return total + pltpu.roll(sum(crossed), HALF, 1) if crossed else total


def _halves_dq_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                      v_ref, do_ref, stats_ref, delta_ref, dq_ref, dq_s, *,
                      scale: float, group: int, block: int,
                      window: Optional[int], sub: int):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    half = _lane_half(block)
    ks = _placed_halves(k_ref[0], half, group)
    vs = _placed_halves(v_ref[0], half, group)
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    for h in range(2 * group):
        _, _, k, _, ds = _halves_backward(
            h, q_ref, do_ref, ks, vs, allowed, half, stats, delta, scale,
            group, own=False)
        dq_s[:, _head(h // 2)] += jnp.dot(
            ds, k, preferred_element_type=jnp.float32)

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _halves_dkv_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, do_ref, stats_ref, delta_ref, dk_ref, dv_ref,
                       dk_s, dv_s, *, scale: float, group: int, block: int,
                       window: Optional[int], sub: int):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    half = _lane_half(block)
    ks = _placed_halves(k_ref[0], half, group)
    vs = _placed_halves(v_ref[0], half, group)
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    dk, dv = [], []
    for h in range(2 * group):
        q, do, _, prob, ds = _halves_backward(
            h, q_ref, do_ref, ks, vs, allowed, half, stats, delta, scale,
            group, own=True)
        crossed = h // group != h % 2
        dv.append((crossed, jnp.dot(prob.astype(do.dtype).T, do,
                                    preferred_element_type=jnp.float32)))
        dk.append((crossed, jnp.dot(ds.T, q,
                                    preferred_element_type=jnp.float32)))
    dk_s[...] += _home(dk)
    dv_s[...] += _home(dv)

    @pl.when(last_ref[p] == 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _halves_bwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, do_ref, stats_ref, delta_ref, dq_ref, dk_ref,
                       dv_ref, dq_s, dk_s, dv_s, *, scale: float, group: int,
                       block: int, window: Optional[int], sub: int):
    """``_causal_bwd_kernel`` for a key-value tile of two heads: one pass
    over the band, ``dk_s`` and ``dv_s`` (T, 128) the tile's whole
    sequence."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    half = _lane_half(block)
    ks = _placed_halves(k_ref[0], half, group)
    vs = _placed_halves(v_ref[0], half, group)
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    keys = pl.ds(pl.multiple_of(ki_ref[p] * block, block), block)
    dk, dv = [], []
    for h in range(2 * group):
        q, do, k, prob, ds = _halves_backward(
            h, q_ref, do_ref, ks, vs, allowed, half, stats, delta, scale,
            group, own=True)
        crossed = h // group != h % 2
        dq_s[:, _head(h // 2)] += jnp.dot(
            ds, k, preferred_element_type=jnp.float32)
        dv.append((crossed, jnp.dot(prob.astype(do.dtype).T, do,
                                    preferred_element_type=jnp.float32)))
        dk.append((crossed, jnp.dot(ds.T, q,
                                    preferred_element_type=jnp.float32)))
    dk_s[keys, :] += _home(dk)
    dv_s[keys, :] += _home(dv)

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call: grid (B, key-value heads, pairs of the band)
# ---------------------------------------------------------------------------

def _call(kernel, operands, outs, scratch, *, t: int, group: int,
          block: int, window: Optional[int], key_major: bool,
          interpret: bool, scale: float = LANES ** -0.5,
          steps: Optional[int] = None, heads_parallel: bool = True,
          v_block: int = 0, more_specs=None, lanes: int = LANES, **static):
    """``operands`` / ``outs``: (array or shape-dtype, kind) with kind "q"
    (a group's query lanes, by query block), "kv" (one key-value head, by
    key block), "kv_all" (one key-value head's whole sequence) or "stats"
    (by query block); for latent attention, whose ``group`` heads have a
    key-value head each, "kv_group" / "kv_group_all" (the group's key or
    value lanes), "q_rope" (the group's rotary query lanes, one tile),
    "k_rope" (the shared rotary key's placement, by key block) and
    "k_rope_all" (its cotangent's whole sequence, the same block for every
    head) and "v_group" (the group's value lanes, ``v_block`` column blocks
    into an array that holds the keys' lanes first); for a selection read
    from the data, "sel" (a (B, T, T) array's tile of the pair). ``steps``:
    the grid's head axis, where it is not the key-value heads of the second
    operand. ``more_specs``: a caller's own kinds, {kind: BlockSpec}.
    ``static``: the kernel's further static keywords (``sub`` of the
    kernels :func:`causal_attention` picks). ``lanes``: a head's lanes in
    the kinds "q", "kv" and "kv_all", where it is over several lane tiles
    (the statistics keep one lane a head)."""
    b = operands[1][0].shape[0]
    g = steps or operands[1][0].shape[2] // lanes
    table = band_pairs(t // block, block, window, key_major)
    specs = {
        "q": pl.BlockSpec((1, block, group * lanes),
                          lambda i, j, p, qi, ki, fi, la: (i, qi[p], j)),
        "kv": pl.BlockSpec((1, block, lanes),
                           lambda i, j, p, qi, ki, fi, la: (i, ki[p], j)),
        "kv_all": pl.BlockSpec((1, t, lanes),
                               lambda i, j, p, qi, ki, fi, la: (i, 0, j)),
        "stats": pl.BlockSpec((1, 1, block, LANES),
                              lambda i, j, p, qi, ki, fi, la:
                              (i, j, qi[p], 0)),
        "kv_group": pl.BlockSpec((1, block, group * LANES),
                                 lambda i, j, p, qi, ki, fi, la:
                                 (i, ki[p], j)),
        "kv_group_all": pl.BlockSpec((1, t, group * LANES),
                                     lambda i, j, p, qi, ki, fi, la:
                                     (i, 0, j)),
        "q_rope": pl.BlockSpec((1, block, LANES),
                               lambda i, j, p, qi, ki, fi, la: (i, qi[p], j)),
        "k_rope": pl.BlockSpec((1, block, group * LANES),
                               lambda i, j, p, qi, ki, fi, la: (i, ki[p], 0)),
        "k_rope_all": pl.BlockSpec((1, t, LANES),
                                   lambda i, j, p, qi, ki, fi, la: (i, 0, 0)),
        "v_group": pl.BlockSpec((1, block, group * LANES),
                                lambda i, j, p, qi, ki, fi, la:
                                (i, ki[p], v_block + j)),
        "sel": pl.BlockSpec((1, block, block),
                            lambda i, j, p, qi, ki, fi, la:
                            (i, qi[p], ki[p])),
        **(more_specs or {}),
    }
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, group=group, block=block,
                          window=window, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, g, len(table)),
            in_specs=[specs[kind] for _, kind in operands],
            out_specs=[specs[kind] for _, kind in outs],
            scratch_shapes=list(scratch)),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel" if heads_parallel else "arbitrary",
                "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*(jnp.asarray(table[:, c]) for c in range(4)),
      *(x for x, _ in operands))


def _stats_like(q, group: int, lanes: int = LANES):
    b, t, width = q.shape
    return jax.ShapeDtypeStruct((b, width // (group * lanes), t, LANES),
                                jnp.float32)


def _wide(head_dim: int) -> dict:
    """What a call for heads over several lane tiles adds to ``_call``'s
    keywords: the tiles' lanes and the kernels' ``width``; nothing for a
    head of one lane tile or half of one (their calls as they were)."""
    return dict(lanes=head_dim, width=head_dim) if head_dim > LANES else {}


def _fwd(q, k, v, window, block, interpret, head_dim):
    group = q.shape[2] // k.shape[2]
    per = max(1, LANES // head_dim)         # heads a lane tile
    lanes = max(LANES, head_dim)            # a head's, or two heads', lanes
    rows = pltpu.VMEM((per * group, block, LANES), jnp.float32)
    return _call(
        _causal_fwd_kernel if per == 1 else _halves_fwd_kernel,
        [(q, "q"), (k, "kv"), (v, "kv")],
        [(q, "q"), (_stats_like(q, group, lanes), "stats")],
        [rows, rows, pltpu.VMEM((block, group * lanes), jnp.float32)],
        t=q.shape[1], group=group, block=block, window=window,
        key_major=False, interpret=interpret, scale=head_dim ** -0.5,
        sub=sub_block(block, head_dim), **_wide(head_dim))


def _padded(x, block: int):
    pad = -x.shape[1] % block
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def causal_attention(q, k, v, window: Optional[int] = None,
                     block: int = BLOCK, interpret: bool = False,
                     head_dim: int = LANES):
    """softmax(q k^T / sqrt(d), key <= query [and query - key < window])
    v, query head ``h`` reading key-value head ``h // (H / G)``, heads of
    ``head_dim`` d = 128 (one a lane tile), 64 (two) or 256 (one over two
    lane tiles: the scores' product 256 deep, the context 256 wide).

    q: (B, T, H*d); k, v: (B, T, G*d). Returns (B, T, H*d). ``T``
    need not be a multiple of ``block``: the rows are padded at the end,
    where causality keeps them out of every real row's sum.
    """
    t = q.shape[1]
    out, _ = _fwd(*(_padded(x, block) for x in (q, k, v)), window, block,
                  interpret, head_dim)
    return out[:, :t]


def _vjp_fwd(q, k, v, window, block, interpret, head_dim):
    t = q.shape[1]
    out, stats = _fwd(*(_padded(x, block) for x in (q, k, v)), window,
                      block, interpret, head_dim)
    # named on the residuals themselves, so that a remat policy can keep
    # them and the backward pass does not run the forward kernel again
    out = checkpoint_name(out[:, :t], "attn_out")
    if max(1, LANES // head_dim) * (q.shape[2] // k.shape[2]) == 1:
        # one query head a key-value head: of a tile's 128 lanes one holds
        # a number. That lane is what a policy keeps (1 / 128 of the array:
        # 64 MiB a layer at 16 heads and 8 192 tokens, which a stack run
        # several times keeps once a pass) and the array is made of it again
        lane = checkpoint_name(stats[..., 0], "attn_stats")
        stats = jnp.pad(lane[..., None], ((0, 0),) * 3 + ((0, LANES - 1),))
    else:
        stats = checkpoint_name(stats, "attn_stats")
    return out, (q, k, v, out, stats)


def _delta(dout, out, heads: int, block: int, head_dim: int = LANES):
    """rowsum(do * o), a head: (B, T, grid steps, ``heads`` a step) -> lanes
    of the statistics' layout, the rows padded to whole blocks."""
    b, t, _ = out.shape
    delta = jnp.sum((dout.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, t, -1, heads, head_dim), axis=-1)
    return jnp.pad(delta.swapaxes(1, 2),
                   ((0, 0), (0, 0), (0, -t % block), (0, LANES - heads)))


def _vjp_bwd(window, block, interpret, head_dim, res, dout):
    q, k, v, out, stats = res
    b, t, width = q.shape
    group = width // k.shape[2]
    per = max(1, LANES // head_dim)
    lanes = max(LANES, head_dim)
    delta = _delta(dout, out, per * group, block, head_dim)
    q, k, v, dout = (_padded(x, block) for x in (q, k, v, dout))
    operands = [(q, "q"), (k, "kv"), (v, "kv"), (dout, "q"),
                (stats, "stats"), (delta, "stats")]
    kw = dict(t=q.shape[1], group=group, block=block, window=window,
              interpret=interpret, scale=head_dim ** -0.5,
              sub=sub_block(block, head_dim), **_wide(head_dim))
    fused, dq_only, dkv_only = (
        (_causal_bwd_kernel, _causal_dq_kernel, _causal_dkv_kernel)
        if per == 1 else
        (_halves_bwd_kernel, _halves_dq_kernel, _halves_dkv_kernel))
    dq_s = pltpu.VMEM((block, group * lanes), jnp.float32)
    if fused_backward_fits(t, group, q.dtype.itemsize, block,
                           lanes=lanes) is None:
        acc = pltpu.VMEM((q.shape[1], lanes), jnp.float32)
        dq, dk, dv = _call(fused, operands,
                           [(q, "q"), (k, "kv_all"), (v, "kv_all")],
                           [dq_s, acc, acc], key_major=False, **kw)
    else:
        (dq,) = _call(dq_only, operands, [(q, "q")], [dq_s],
                      key_major=False, **kw)
        acc = pltpu.VMEM((block, lanes), jnp.float32)
        dk, dv = _call(dkv_only, operands, [(k, "kv"), (v, "kv")],
                       [acc, acc], key_major=True, **kw)
    return dq[:, :t], dk[:, :t], dv[:, :t]


causal_attention.defvjp(_vjp_fwd, _vjp_bwd)


# ---------------------------------------------------------------------------
# Latent attention: scores from two products, one shared rotary key
# ---------------------------------------------------------------------------

LATENT_HEADS = 2                  # heads a grid step: 2 x 64 rotary lanes
ROPE_LANES = LANES // LATENT_HEADS
LATENT_SCALE = (LANES + ROPE_LANES) ** -0.5


def latent_fits(tokens: int, heads: int, nope: int, rope: int, value: int,
                itemsize: int, block: int = BLOCK) -> Optional[str]:
    """None where the latent kernels take these local shapes (``heads``
    heads of ``nope`` + ``rope`` query-key lanes and ``value`` value lanes,
    ``tokens`` a sample, operands of ``itemsize`` bytes), else why not."""
    if (nope, rope, value) != (LANES, ROPE_LANES, LANES):
        return (f"heads of {nope} + {rope} | {value} lanes are not "
                f"{LANES} + {ROPE_LANES} | {LANES}")
    if heads % LATENT_HEADS:
        return f"{heads} heads are not pairs (two rotary parts a lane tile)"
    t = tokens + -tokens % block
    wide, tile = LATENT_HEADS * LANES, block * LANES
    need = (2 * t * wide * 4                    # dk_nope, dv accumulators
            + 2 * 2 * t * wide * itemsize       # their outputs, two buffers
            + t * LANES * 4 * 3                 # dk_rope: one, and its output
            + (LATENT_HEADS + 1) * tile * 4     # dq's accumulators
            + 3 * 2 * (LATENT_HEADS + 1) * tile * itemsize   # q, do, dq
            + 4 * 2 * LATENT_HEADS * tile * itemsize         # k, v, k_rope, o
            + (2 + LATENT_HEADS) * tile * 4     # statistics, the deltas
            + 4 * block * block * 4)            # scores, p, dp, ds
    if need > VMEM_LIMIT_BYTES:
        return (f"dk and dv of {t} tokens, two heads a step, need "
                f"{need / 2 ** 20:.1f} MiB of VMEM, over "
                f"{VMEM_LIMIT_BYTES / 2 ** 20:g}")
    return None


def _latent_scores(q_nope, q_rope, k_nope, k_rope, scale):
    """(block, block) f32: one head's scores. ``q_rope``: the step's two
    heads' rotary parts; ``k_rope``: the shared key placed in this head's
    half of the lanes, zeros in the other's."""
    return (jax.lax.dot_general(q_nope, k_nope, _NT,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(q_rope, k_rope, _NT,
                                  preferred_element_type=jnp.float32)) * scale


def _latent_fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, qn_ref, qr_ref,
                       kn_ref, kr_ref, v_ref, o_ref, stats_ref, m_s, l_s,
                       acc_s, *, scale: float, group: int, block: int,
                       window: Optional[int]):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    q_rope = qr_ref[0]
    for h in range(group):
        s = _latent_scores(qn_ref[0, :, _head(h)], q_rope,
                           kn_ref[0, :, _head(h)], kr_ref[0, :, _head(h)],
                           scale)
        _softmax_step(h, jnp.where(allowed, s, MASK_VALUE),
                      v_ref[0, :, _head(h)], m_s, l_s, acc_s, block)

    @pl.when(last_ref[p] == 1)
    def _():
        _write_forward(o_ref, stats_ref, m_s, l_s, acc_s, group, block)


def _latent_bwd_kernel(qi_ref, ki_ref, first_ref, last_ref, qn_ref, qr_ref,
                       kn_ref, kr_ref, v_ref, do_ref, o_ref, stats_ref,
                       dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref, dqn_s,
                       dqr_s, dkn_s, dkr_s, dv_s, delta_s, *, scale: float,
                       group: int, block: int, window: Optional[int]):
    """Every cotangent from one pass over the band, query-block-major:
    ``dqn_s`` / ``dqr_s`` hold a query block over its key blocks, as
    ``delta_s`` holds each head's rowsum(do * o) of it, on every lane;
    ``dkn_s`` / ``dv_s`` (T, group * 128) the step's heads' whole sequence
    over all pairs, ``dkr_s`` (T, 128) the shared key's over all pairs and
    all heads of a sample."""
    j, p = pl.program_id(1), pl.program_id(2)
    start, end = p == 0, p == pl.num_programs(2) - 1

    @pl.when(start)
    def _():
        dkn_s[...] = jnp.zeros(dkn_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(start & (j == 0))
    def _():
        dkr_s[...] = jnp.zeros(dkr_s.shape, jnp.float32)

    @pl.when(first_ref[p] == 1)
    def _():
        dqn_s[...] = jnp.zeros(dqn_s.shape, jnp.float32)
        dqr_s[...] = jnp.zeros(dqr_s.shape, jnp.float32)
        for h in range(group):
            delta_s[h] = jnp.broadcast_to(jnp.sum(
                do_ref[0, :, _head(h)].astype(jnp.float32)
                * o_ref[0, :, _head(h)].astype(jnp.float32),
                axis=1, keepdims=True), (block, LANES))

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    stats = stats_ref[0, 0]
    keys = pl.ds(pl.multiple_of(ki_ref[p] * block, block), block)
    q_rope = qr_ref[0]
    half = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1) \
        // ROPE_LANES
    dk_rope = []
    for h in range(group):
        q_nope, do = qn_ref[0, :, _head(h)], do_ref[0, :, _head(h)]
        k_nope, k_rope = kn_ref[0, :, _head(h)], kr_ref[0, :, _head(h)]
        s = _latent_scores(q_nope, q_rope, k_nope, k_rope, scale)
        prob = jnp.exp(jnp.where(allowed, s, MASK_VALUE) - stats[:, h:h + 1])
        dp = jax.lax.dot_general(do, v_ref[0, :, _head(h)], _NT,
                                 preferred_element_type=jnp.float32)
        # cast to the operands' dtype once, and transpose the narrow copy
        ds = (prob * (dp - delta_s[h][:, :1]) * scale).astype(k_nope.dtype)
        dqn_s[:, _head(h)] += jnp.dot(ds, k_nope,
                                      preferred_element_type=jnp.float32)
        # the other head's half of k_rope is zeros: its lanes stay
        dqr_s[...] += jnp.dot(ds, k_rope, preferred_element_type=jnp.float32)
        dv_s[keys, _head(h)] += jnp.dot(prob.astype(do.dtype).T, do,
                                        preferred_element_type=jnp.float32)
        dkn_s[keys, _head(h)] += jnp.dot(ds.T, q_nope,
                                         preferred_element_type=jnp.float32)
        dk_rope.append(jnp.dot(ds.T, q_rope,
                               preferred_element_type=jnp.float32))
    # ds^T q_rope is a head's in its own half of the lanes only
    dkr_s[keys, :] += jnp.where(half == 0, *dk_rope)

    @pl.when(last_ref[p] == 1)
    def _():
        dqn_ref[0] = dqn_s[...].astype(dqn_ref.dtype)
        dqr_ref[0] = dqr_s[...].astype(dqr_ref.dtype)

    @pl.when(end)
    def _():
        dkn_ref[0] = dkn_s[...].astype(dkn_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)

    @pl.when(end & (j == pl.num_programs(1) - 1))
    def _():
        dkr_ref[0] = dkr_s[...]


def _placed(k_rope):
    """(B, T, 256): ``[k_rope, 0 | 0, k_rope]``, the shared key in head 0's
    half of one lane tile and in head 1's half of the next."""
    zeros = jnp.zeros_like(k_rope)
    return jnp.concatenate([k_rope, zeros, zeros, k_rope], axis=2)


def _keys_and_values(kv):
    """The kernels' key and value operands, and the column block of
    ``LATENT_HEADS`` heads at which the values start in theirs: ``kv`` is
    one (B, T, H*256) array, every head's ``k_nope`` and then every head's
    ``v``, read twice where it lies, or the pair ``(k_nope, v)``."""
    if isinstance(kv, tuple):
        return (*kv, 0)
    return kv, kv, kv.shape[2] // (2 * LATENT_HEADS * LANES)


def _pad_all(operands, block: int):
    return jax.tree.map(lambda x: _padded(x, block), operands)


def _latent_fwd(q, q_rope, kv, k_rope, block, interpret):
    group = LATENT_HEADS
    k, v, v_block = _keys_and_values(kv)
    (b, t, _), steps = q.shape, q_rope.shape[2] // LANES
    out = jax.ShapeDtypeStruct((b, t, steps * group * LANES), v.dtype)
    rows = pltpu.VMEM((group, block, LANES), jnp.float32)
    return _call(
        _latent_fwd_kernel,
        [(q, "q"), (q_rope, "q_rope"), (k, "kv_group"),
         (_placed(k_rope), "k_rope"), (v, "v_group")],
        [(out, "q"), (_stats_like(out, group), "stats")],
        [rows, rows, pltpu.VMEM((block, group * LANES), jnp.float32)],
        t=t, group=group, block=block, window=None, key_major=False,
        interpret=interpret, scale=LATENT_SCALE, steps=steps,
        v_block=v_block)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def latent_attention(q, q_rope, kv, k_rope, block: int = BLOCK,
                     interpret: bool = False):
    """softmax((q_nope k_nope^T + q_rope k_rope^T) / sqrt(192), key <=
    query) v, head ``h`` reading its own ``k_nope`` and ``v`` and the one
    ``k_rope``.

    q: (B, T, H*128) or wider: every head's ``nope`` lanes first, whatever
    follows them (``q_b``'s output, the unrotated ``rope`` lanes last) is
    not read and gets a cotangent of noughts; q_rope: (B, T, H*64), rotated,
    head-major; kv: (B, T, H*256), every head's ``k_nope`` and then every
    head's ``v``, or the pair ``(k_nope, v)`` of (B, T, H*128) each;
    k_rope: (B, T, 64), rotated. Returns (B, T, H*128). ``T`` is padded as
    :func:`causal_attention` pads it.
    """
    t = q.shape[1]
    out, _ = _latent_fwd(*_pad_all((q, q_rope, kv, k_rope), block), block,
                         interpret)
    return out[:, :t]


def _latent_vjp_fwd(q, q_rope, kv, k_rope, block, interpret):
    t = q.shape[1]
    out, stats = _latent_fwd(*_pad_all((q, q_rope, kv, k_rope), block),
                             block, interpret)
    out = checkpoint_name(out[:, :t], "attn_out")
    stats = checkpoint_name(stats, "attn_stats")
    return out, (q, q_rope, kv, k_rope, out, stats)


def _latent_vjp_bwd(block, interpret, res, dout):
    q, q_rope, kv, k_rope, out, stats = res
    (_, t, width), group = q.shape, LATENT_HEADS
    q, q_rope, kv, k_rope, out, dout = _pad_all(
        (q, q_rope, kv, k_rope, out, dout), block)
    k, v, v_block = _keys_and_values(kv)
    b, padded, _ = out.shape
    dq_like = jax.ShapeDtypeStruct(out.shape, q.dtype)
    dkv_like = jax.ShapeDtypeStruct(out.shape, k.dtype)
    dk_rope_like = jax.ShapeDtypeStruct((b, padded, LANES), jnp.float32)
    wide = pltpu.VMEM((padded, group * LANES), jnp.float32)
    dq_nope, dq_rope, dk_nope, dk_rope, dv = _call(
        _latent_bwd_kernel,
        [(q, "q"), (q_rope, "q_rope"), (k, "kv_group"),
         (_placed(k_rope), "k_rope"), (v, "v_group"), (dout, "q"),
         (out, "q"), (stats, "stats")],
        [(dq_like, "q"), (q_rope, "q_rope"), (dkv_like, "kv_group_all"),
         (dk_rope_like, "k_rope_all"), (dkv_like, "kv_group_all")],
        [pltpu.VMEM((block, group * LANES), jnp.float32),
         pltpu.VMEM((block, LANES), jnp.float32), wide,
         pltpu.VMEM((padded, LANES), jnp.float32), wide,
         pltpu.VMEM((group, block, LANES), jnp.float32)],
        t=padded, group=group, block=block, window=None, key_major=False,
        interpret=interpret, scale=LATENT_SCALE,
        steps=q_rope.shape[2] // LANES, heads_parallel=False,
        v_block=v_block)
    # the lanes of q after the heads' nope parts are not this call's: one
    # pad of noughts, which XLA fuses into the sum with the rotary's
    dq = dq_nope[:, :t]
    if width > dq.shape[2]:
        dq = jnp.pad(dq, ((0, 0), (0, 0), (0, width - dq.shape[2])))
    dkv = dk_nope[:, :t], dv[:, :t]
    if not isinstance(kv, tuple):
        dkv = jnp.concatenate(dkv, axis=2)
    # the shared key's cotangent: the two heads' halves of every step's sum
    dk_rope = (dk_rope[..., :ROPE_LANES]
               + dk_rope[..., ROPE_LANES:]).astype(k_rope.dtype)
    return dq, dq_rope[:, :t], dkv, dk_rope[:, :t]


latent_attention.defvjp(_latent_vjp_fwd, _latent_vjp_bwd)


# ---------------------------------------------------------------------------
# Attention over a selected set: the allowed pairs are data
# ---------------------------------------------------------------------------
#
# ``sel`` (B, T, T) f32 holds, for query t and key s, a score where s is in
# t's set and ``MASK_VALUE`` where it is not (every s > t among those): what
# an indexer's selection writes (models/sparse_lm.py). The kernels walk the
# causal band's tiles as ``causal_attention`` does, every one of them (a
# set of thousands of keys scattered over the keys before a query leaves no
# tile of 512 x 512 empty, and a walk that depended on the data would make
# the step's time depend on it), and read a tile's allowed pairs from
# ``sel``'s tile instead of computing them from the indices: one more
# (block, block) f32 operand a grid step. Everything else is
# ``_causal_fwd_kernel``'s and ``_causal_bwd_kernel``'s: the tile
# arithmetic, ``_softmax_step``, the statistics' layout, ``_tile_backward``,
# ``_call``. A tile that holds no chosen pair folds nothing in: its masked
# scores are ``MASK_VALUE``, which the first chosen key's score wipes
# (``alpha`` = exp(MASK_VALUE - m) = 0) and which adds exp(MASK_VALUE - m)
# = 0 after it. Every real query has a chosen key (a set is never empty).

def selected_fits(tokens: int, q_width: int, kv_width: int, head_dim: int,
                  itemsize: int, block: int = BLOCK) -> Optional[str]:
    """None where the selected-set kernels take these local shapes, else
    why not: one 128-wide head a lane tile, the one-kernel backward (no
    split form is written for a selection), and the mean over the heads
    with every head of a sample in one grid step, in the larger of its two
    forms: the one that writes the mean's tiles (the other keeps five
    (block, 128) sums and writes one such block of rows)."""
    if head_dim != LANES:
        return f"head_dim {head_dim} is not one {LANES}-lane tile"
    why_not = blockwise_fits(q_width, kv_width, head_dim)
    if why_not is None:
        why_not = fused_backward_fits(tokens, q_width // kv_width, itemsize,
                                      block, selected=True)
    if why_not is None:
        tile = block * block * 4
        need = (2 * block * (q_width + kv_width) * itemsize   # q, k tiles
                + 2 * (kv_width // LANES) * block * LANES * 4  # statistics
                + 2 * tile                                     # sel
                + max(2 * tile, 7 * block * LANES * 4)  # the mean | the rows
                + 4 * tile)                     # s, p, the heads' sum
        if need > VMEM_LIMIT_BYTES:
            why_not = (f"every head's tile of the mean needs "
                       f"{need / 2 ** 20:.1f} MiB of VMEM, over "
                       f"{VMEM_LIMIT_BYTES / 2 ** 20:g}")
    return why_not


def _chosen(sel_ref):
    """(block, block) bool: the tile's pairs that the selection allows."""
    return sel_ref[0] > MASK_VALUE


def _selected_fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                         v_ref, sel_ref, o_ref, stats_ref, m_s, l_s, acc_s,
                         *, scale: float, group: int, block: int,
                         window: Optional[int]):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    allowed = _chosen(sel_ref)
    k, v = k_ref[0], v_ref[0]
    for h in range(group):
        s = jax.lax.dot_general(q_ref[0, :, _head(h)], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        _softmax_step(h, jnp.where(allowed, s, MASK_VALUE), v, m_s, l_s,
                      acc_s, block)

    @pl.when(last_ref[p] == 1)
    def _():
        _write_forward(o_ref, stats_ref, m_s, l_s, acc_s, group, block)


def _selected_bwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                         v_ref, sel_ref, do_ref, stats_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_s, dk_s, dv_s, *,
                         scale: float, group: int, block: int,
                         window: Optional[int]):
    """``_causal_bwd_kernel`` with the tile's allowed pairs read from
    ``sel``: one pass over the band, ``dk_s`` and ``dv_s`` (T, 128) a
    key-value head's whole sequence."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    allowed = _chosen(sel_ref)
    k, v = k_ref[0], v_ref[0]
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    keys = pl.ds(pl.multiple_of(ki_ref[p] * block, block), block)
    for h in range(group):
        q, do = q_ref[0, :, _head(h)], do_ref[0, :, _head(h)]
        prob, ds = _tile_backward(q, do, k, v, allowed, stats[:, h:h + 1],
                                  delta[:, h:h + 1], scale)
        ds = ds.astype(k.dtype)
        dq_s[:, _head(h)] += jnp.dot(ds, k,
                                     preferred_element_type=jnp.float32)
        dv_s[keys, :] += jnp.dot(prob.astype(do.dtype).T, do,
                                 preferred_element_type=jnp.float32)
        dk_s[keys, :] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# lanes of the loss's rows (:func:`selected_loss_rows`)
KL_LANE, LSE_LANE, COUNT_LANE = 0, 1, 2
_TINY = float(np.finfo(np.float32).tiny)


def _slabs(x):
    """A (block, W) tile's 128-lane column slabs: whole registers."""
    return [x[:, _head(c)] for c in range(x.shape[1] // LANES)]


def _selected_mean_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                          stats_ref, sel_ref, o_ref, *sums, scale: float,
                          group: int, block: int, window: Optional[int],
                          rows: bool = False):
    """A tile of the heads' mean probability: every key-value head of a
    sample and its ``group`` query heads in one grid step, each head's
    probabilities from the forward's statistics, none of them written.

    ``rows``: the tile is not written either, but summed into the indexer's
    loss a row. ``sums`` (m, l, kl, p, n: (block, 128) f32 each) hold a row
    block over its tiles, lane j the tile columns j mod 128: the running
    maximum of ``sel`` and sum of ``exp(sel - m)`` (``_softmax_step``'s
    ``m`` and ``l``, a lane; a lane's masked scores are wiped as a tile's
    are there), the sums of ``tot (log tot - sel)`` and of ``tot``, the
    heads' sum ``tot`` = H ``pbar``, and the count of the set. At the row
    block's last tile the lanes are joined and ``o_ref`` (1, block, 128)
    gets, a row, KL(pbar || softmax of ``sel`` over the set) = sum pbar
    (log pbar - sel) + lse sum pbar in :data:`KL_LANE`, the log-sum-exp in
    :data:`LSE_LANE`, the count in :data:`COUNT_LANE`."""
    sel = sel_ref[0]                      # (the rows form reads it too)
    allowed = sel > MASK_VALUE
    heads = k_ref.shape[2] // LANES
    total = jnp.zeros((block, block), jnp.float32)
    for g in range(heads):
        k, stats = k_ref[0, :, _head(g)], stats_ref[0, g]
        for h in range(group):
            s = jax.lax.dot_general(
                q_ref[0, :, _head(g * group + h)], k, _NT,
                preferred_element_type=jnp.float32) * scale
            total += jnp.exp(jnp.where(allowed, s, MASK_VALUE)
                             - stats[:, h:h + 1])
    if not rows:
        o_ref[0] = total * (1.0 / (heads * group))
        return

    # A tile's sums are kept a lane, lane j the tile's columns j mod 128:
    # its column slabs added register to register. (A lane reduction of
    # such a tile by the XLU costs five products of its size and a sum as a
    # product with ones one, PERF.md section 6, PR 58, and the MXU and the
    # vector unit are this kernel's busiest.) The lanes are joined once a
    # row block, and the heads' sum is divided there.
    p = pl.program_id(2)
    m_s, l_s, kl_s, p_s, n_s = sums

    @pl.when(first_ref[p] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        for ref in (l_s, kl_s, p_s, n_s):
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    added = lambda xs: functools.reduce(jnp.add, xs)
    x, tot = _slabs(sel), _slabs(total)
    m_prev = m_s[...]
    m_next = functools.reduce(jnp.maximum, x, m_prev)
    l_s[...] = jnp.exp(m_prev - m_next) * l_s[...] + added(
        [jnp.exp(xc - m_next) for xc in x])
    m_s[...] = m_next
    # xlogy(0, 0) = 0, and off the set 0 * (a finite number)
    kl_s[...] += added([tc * (jnp.log(jnp.maximum(tc, _TINY)) - xc)
                        for tc, xc in zip(tot, x)])
    p_s[...] += added(tot)
    # (compared again a slab: 0.02 ms a call faster than slabs of the mask)
    n_s[...] += added([jnp.where(xc > MASK_VALUE, 1.0, 0.0) for xc in x])

    @pl.when(last_ref[p] == 1)
    def _():
        # a row's sum over the lanes, on every lane: a product with ones
        ones = jnp.ones((LANES, LANES), jnp.float32)
        summed = lambda x: jnp.dot(x, ones,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        m = jnp.max(m_s[...], axis=1, keepdims=True)
        lse = m + jnp.log(summed(l_s[...] * jnp.exp(m_s[...] - m)))
        n, each = summed(n_s[...]), 1.0 / (heads * group)
        # pbar = the heads' sum * each: sum pbar (log pbar - sel)
        #   = each * (sum tot (log tot - sel) + log(each) sum tot)
        pbar = summed(p_s[...]) * each
        kl = each * summed(kl_s[...]) + (np.log(each) + lse) * pbar
        lane = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1)
        # (a padded row's statistics are its masked scores': its "mean" is
        # no nought, and its sums are dropped here)
        o_ref[0] = jnp.where(
            lane == KL_LANE, jnp.where(n > 0.0, kl, 0.0), jnp.where(
                lane == LSE_LANE, lse, jnp.where(lane == COUNT_LANE, n, 0.0)))


def _padded_sel(sel, block: int):
    pad = -sel.shape[1] % block
    return jnp.pad(sel, ((0, 0), (0, pad), (0, pad)),
                   constant_values=MASK_VALUE) if pad else sel


def selected_forward(q, k, v, sel, block: int = BLOCK,
                     interpret: bool = False):
    """:func:`selected_attention`'s two results with no derivative rule: for
    a caller that keeps them and calls :func:`selected_backward` itself."""
    t = q.shape[1]
    q, k, v = (_padded(x, block) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    rows = pltpu.VMEM((group, block, LANES), jnp.float32)
    out, stats = _call(
        _selected_fwd_kernel,
        [(q, "q"), (k, "kv"), (v, "kv"), (_padded_sel(sel, block), "sel")],
        [(q, "q"), (_stats_like(q, group), "stats")],
        [rows, rows, pltpu.VMEM((block, group * LANES), jnp.float32)],
        t=q.shape[1], group=group, block=block, window=None,
        key_major=False, interpret=interpret)
    return out[:, :t], stats


def selected_backward(q, k, v, sel, out, stats, dout, block: int = BLOCK,
                      interpret: bool = False):
    """Cotangents of (q, k, v) from :func:`selected_forward`'s results and
    the output's cotangent: one kernel a tile."""
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    delta = _delta(dout, out, group, block)
    q, k, v, dout = (_padded(x, block) for x in (q, k, v, dout))
    acc = pltpu.VMEM((q.shape[1], LANES), jnp.float32)
    dq, dk, dv = _call(
        _selected_bwd_kernel,
        [(q, "q"), (k, "kv"), (v, "kv"), (_padded_sel(sel, block), "sel"),
         (dout, "q"), (stats, "stats"), (delta, "stats")],
        [(q, "q"), (k, "kv_all"), (v, "kv_all")],
        [pltpu.VMEM((block, group * LANES), jnp.float32), acc, acc],
        t=q.shape[1], group=group, block=block, window=None,
        key_major=False, interpret=interpret)
    return dq[:, :t], dk[:, :t], dv[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def selected_attention(q, k, v, sel, block: int = BLOCK,
                       interpret: bool = False):
    """softmax(q k^T / sqrt(128), over the keys of the query's set) v, query
    head ``h`` reading key-value head ``h // (H / G)``, and the row
    statistics ``L = m + log(sum exp(s - m))`` that
    :func:`selected_mean_probs` reads.

    q: (B, T, H*128); k, v: (B, T, G*128); sel: (B, T, T) f32, a score where
    the key is in the query's set and ``MASK_VALUE`` where it is not (no
    gradient passes to it). Returns ((B, T, H*128), (B, G, T', 128) f32),
    T' = T padded to whole blocks."""
    return selected_forward(q, k, v, sel, block, interpret)


def _selected_vjp_fwd(q, k, v, sel, block, interpret):
    out, stats = selected_forward(q, k, v, sel, block, interpret)
    out = checkpoint_name(out, "attn_out")
    stats = checkpoint_name(stats, "attn_stats")
    return (out, stats), (q, k, v, sel, out, stats)


def _selected_vjp_bwd(block, interpret, res, cotangents):
    q, k, v, sel, out, stats = res
    dout, _ = cotangents                 # the statistics carry no gradient
    # the caller hands ``sel`` in with its gradient stopped: its noughts
    # are read by nothing
    return (*selected_backward(q, k, v, sel, out, stats, dout, block,
                               interpret), jnp.zeros_like(sel))


selected_attention.defvjp(_selected_vjp_fwd, _selected_vjp_bwd)


def _mean_call(q, k, stats, sel, block: int, interpret: bool, rows: bool):
    """:func:`_selected_mean_kernel` over the band, in either form."""
    group = q.shape[2] // k.shape[2]
    q, k, sel = _padded(q, block), _padded(k, block), _padded_sel(sel, block)
    b, t, _ = q.shape
    heads = k.shape[2] // LANES
    spec = lambda shape, at: pl.BlockSpec(
        shape, lambda i, j, p, qi, ki, fi, la: at(i, qi[p], ki[p]))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    like = (f32(b, t, LANES), "rows") if rows else (f32(*sel.shape), "sel")
    (out,) = _call(
        _selected_mean_kernel,
        [(q, "q_every"), (k, "kv_every"), (stats, "stats_every"),
         (sel, "sel")],
        [like], [pltpu.VMEM((block, LANES), jnp.float32)] * 5 if rows else [],
        t=t, group=group, block=block, window=None,
        key_major=False, interpret=interpret, steps=1, rows=rows,
        more_specs={
            "q_every": spec((1, block, q.shape[2]),
                            lambda i, qb, kb: (i, qb, 0)),
            "kv_every": spec((1, block, k.shape[2]),
                             lambda i, qb, kb: (i, kb, 0)),
            "stats_every": spec((1, heads, block, LANES),
                                lambda i, qb, kb: (i, 0, qb, 0)),
            "rows": spec((1, block, LANES), lambda i, qb, kb: (i, qb, 0))})
    return out


def selected_mean_probs(q, k, stats, sel, block: int = BLOCK,
                        interpret: bool = False):
    """(B, T', T') f32, T' = T padded to whole blocks as the statistics
    are: the mean over the H query heads of each head's attention
    probabilities, from :func:`selected_attention`'s statistics; noughts
    where the key is not in the query's set, and *unwritten* in the tiles
    above the causal band (mask by ``sel``). No gradient: the caller stops
    it."""
    return _mean_call(q, k, stats, sel, block, interpret, False)


def selected_loss_rows(q, k, stats, sel, block: int = BLOCK,
                       interpret: bool = False):
    """(B, T', 128) f32, T' = T padded to whole blocks as the statistics
    are; a row: KL(pbar || softmax of ``sel`` over the row's set) in
    :data:`KL_LANE`, ``pbar`` :func:`selected_mean_probs`' mean; the
    log-sum-exp of ``sel`` over the set in :data:`LSE_LANE`; the keys in the
    set in :data:`COUNT_LANE` (a padded row: 0, its masked scores', 0). The
    mean's kernel with the tiles summed where they are made: no (T, T) array
    is written. No gradient."""
    return _mean_call(q, k, stats, sel, block, interpret, True)
