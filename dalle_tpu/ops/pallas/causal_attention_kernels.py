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
tokens-major arrays, one 128-wide head a lane tile (``head_dim`` is 128):
``q`` (B, T, H*128), ``k``/``v`` (B, T, G*128) for G key-value heads. One
grid step takes a key-value head's (block, 128) tiles of ``k`` and ``v``
once and the ``H / G`` query heads that read them, side by side in the
(block, H/G * 128) tile of ``q``: a key-value tile is fetched once for its
whole group, and ``dk``/``dv`` are summed over the group in VMEM. The row
statistics are (B, G, T, 128) f32 with query head ``h`` of the group in
lane ``h``.

Scores, softmax and statistics are f32; the MXU operands are in the
operands' dtype (bf16 in training).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK = 512
MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
# what every call asks of the compiler, and what the one-kernel backward's
# accumulators have to fit in (tests shrink it to reach the split kernels)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_NT = (((1,), (1,)), ((), ()))       # a @ b.T


def blockwise_fits(q_width: int, kv_width: int, head_dim: int) -> Optional[str]:
    """None where the kernels take these local shapes, else why not."""
    if head_dim != LANES:
        return f"head_dim {head_dim} is not one {LANES}-lane tile"
    if q_width % kv_width or kv_width % LANES:
        return f"{q_width} query lanes over {kv_width} key-value lanes"
    if q_width // kv_width > LANES:
        return "more query heads a group than statistics lanes"
    return None


def fused_backward_fits(tokens: int, group: int, itemsize: int,
                        block: int = BLOCK) -> Optional[str]:
    """None where the one-kernel backward holds a key-value head's whole
    ``dk`` and ``dv`` in VMEM at these local shapes (``tokens`` a sample,
    ``group`` query heads a key-value head, operands of ``itemsize``
    bytes), else why not: then the backward is the ``dq`` and the
    ``dk``/``dv`` kernel, which hold a block each."""
    t = tokens + -tokens % block
    tile = block * LANES
    need = (2 * t * LANES * 4                   # dk, dv accumulators, f32
            + 2 * 2 * t * LANES * itemsize      # their outputs, two buffers
            + group * tile * 4                  # dq's accumulator
            + 3 * 2 * group * tile * itemsize   # q, do, dq tiles
            + 2 * 2 * tile * itemsize           # k, v tiles
            + 2 * 2 * tile * 4                  # statistics, delta
            + 4 * block * block * 4)            # scores, p, dp, ds
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


def _allowed(qi, ki, block: int, window: Optional[int]):
    """(block, block) bool: which (query row, key column) of this tile may
    attend. One mask a grid step, shared by the group's heads."""
    rel = (qi - ki) * block \
        + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0) \
        - jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ok = rel >= 0
    return ok if window is None else ok & (rel < window)


def _head(h: int) -> slice:
    return slice(h * LANES, (h + 1) * LANES)


def _tile_backward(q, do, k, v, allowed, lse, delta, scale):
    """One head's (block, block) tile in the backward: its probabilities
    from the saved statistics, and the scores' cotangent (times ``scale``,
    so that it is q's and k's), both f32."""
    s = jax.lax.dot_general(q, k, _NT,
                            preferred_element_type=jnp.float32) * scale
    prob = jnp.exp(jnp.where(allowed, s, MASK_VALUE) - lse)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    return prob, prob * (dp - delta) * scale


def _causal_fwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, o_ref, stats_ref, m_s, l_s, acc_s, *,
                       scale: float, group: int, block: int,
                       window: Optional[int]):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    k, v = k_ref[0], v_ref[0]
    for h in range(group):
        s = jax.lax.dot_general(q_ref[0, :, _head(h)], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(allowed, s, MASK_VALUE)
        m_prev, l_prev = m_s[h], l_s[h]                  # (block, 128)
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        e = jnp.exp(s - jnp.tile(m_next, (1, block // LANES)))
        alpha = jnp.exp(m_prev - m_next)
        l_s[h] = alpha * l_prev + jnp.sum(e, axis=1)[:, None]
        m_s[h] = m_next
        acc_s[:, _head(h)] = alpha * acc_s[:, _head(h)] + jnp.dot(
            e.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(last_ref[p] == 1)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1)
        stats = jnp.zeros((block, LANES), jnp.float32)
        for h in range(group):
            l = l_s[h]
            o_ref[0, :, _head(h)] = (acc_s[:, _head(h)] / l).astype(
                o_ref.dtype)
            stats = jnp.where(lane == h, m_s[h] + jnp.log(l), stats)
        stats_ref[0, 0] = stats


def _causal_dq_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                      v_ref, do_ref, stats_ref, delta_ref, dq_ref, dq_s, *,
                      scale: float, group: int, block: int,
                      window: Optional[int]):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    k, v = k_ref[0], v_ref[0]
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    for h in range(group):
        _, ds = _tile_backward(q_ref[0, :, _head(h)], do_ref[0, :, _head(h)],
                               k, v, allowed, stats[:, h:h + 1],
                               delta[:, h:h + 1], scale)
        dq_s[:, _head(h)] += jnp.dot(ds.astype(k.dtype), k,
                                     preferred_element_type=jnp.float32)

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _causal_dkv_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, do_ref, stats_ref, delta_ref, dk_ref, dv_ref,
                       dk_s, dv_s, *, scale: float, group: int, block: int,
                       window: Optional[int]):
    p = pl.program_id(2)

    @pl.when(first_ref[p] == 1)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    k, v = k_ref[0], v_ref[0]
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    for h in range(group):
        q, do = q_ref[0, :, _head(h)], do_ref[0, :, _head(h)]
        prob, ds = _tile_backward(q, do, k, v, allowed, stats[:, h:h + 1],
                                  delta[:, h:h + 1], scale)
        dv_s[...] += jnp.dot(prob.T.astype(do.dtype), do,
                             preferred_element_type=jnp.float32)
        dk_s[...] += jnp.dot(ds.T.astype(q.dtype), q,
                             preferred_element_type=jnp.float32)

    @pl.when(last_ref[p] == 1)
    def _():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _causal_bwd_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                       v_ref, do_ref, stats_ref, delta_ref, dq_ref, dk_ref,
                       dv_ref, dq_s, dk_s, dv_s, *, scale: float, group: int,
                       block: int, window: Optional[int]):
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

    allowed = _allowed(qi_ref[p], ki_ref[p], block, window)
    k, v = k_ref[0], v_ref[0]
    stats, delta = stats_ref[0, 0], delta_ref[0, 0]
    keys = pl.ds(pl.multiple_of(ki_ref[p] * block, block), block)
    for h in range(group):
        q, do = q_ref[0, :, _head(h)], do_ref[0, :, _head(h)]
        prob, ds = _tile_backward(q, do, k, v, allowed, stats[:, h:h + 1],
                                  delta[:, h:h + 1], scale)
        # cast to the operands' dtype once, and transpose the narrow copy
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


# ---------------------------------------------------------------------------
# pallas_call: grid (B, key-value heads, pairs of the band)
# ---------------------------------------------------------------------------

def _call(kernel, operands, outs, scratch, *, t: int, group: int,
          block: int, window: Optional[int], key_major: bool,
          interpret: bool):
    """``operands`` / ``outs``: (array or shape-dtype, kind) with kind "q"
    (a group's query lanes, by query block), "kv" (one key-value head, by
    key block), "kv_all" (one key-value head's whole sequence) or "stats"
    (by query block)."""
    b, g = operands[1][0].shape[0], operands[1][0].shape[2] // LANES
    table = band_pairs(t // block, block, window, key_major)
    specs = {
        "q": pl.BlockSpec((1, block, group * LANES),
                          lambda i, j, p, qi, ki, fi, la: (i, qi[p], j)),
        "kv": pl.BlockSpec((1, block, LANES),
                           lambda i, j, p, qi, ki, fi, la: (i, ki[p], j)),
        "kv_all": pl.BlockSpec((1, t, LANES),
                               lambda i, j, p, qi, ki, fi, la: (i, 0, j)),
        "stats": pl.BlockSpec((1, 1, block, LANES),
                              lambda i, j, p, qi, ki, fi, la:
                              (i, j, qi[p], 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, scale=LANES ** -0.5, group=group,
                          block=block, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, g, len(table)),
            in_specs=[specs[kind] for _, kind in operands],
            out_specs=[specs[kind] for _, kind in outs],
            scratch_shapes=list(scratch)),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x, _ in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*(jnp.asarray(table[:, c]) for c in range(4)),
      *(x for x, _ in operands))


def _stats_like(q, group: int):
    b, t, width = q.shape
    return jax.ShapeDtypeStruct((b, width // (group * LANES), t, LANES),
                                jnp.float32)


def _fwd(q, k, v, window, block, interpret):
    group = q.shape[2] // k.shape[2]
    rows = pltpu.VMEM((group, block, LANES), jnp.float32)
    return _call(
        _causal_fwd_kernel, [(q, "q"), (k, "kv"), (v, "kv")],
        [(q, "q"), (_stats_like(q, group), "stats")],
        [rows, rows, pltpu.VMEM((block, group * LANES), jnp.float32)],
        t=q.shape[1], group=group, block=block, window=window,
        key_major=False, interpret=interpret)


def _padded(x, block: int):
    pad = -x.shape[1] % block
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_attention(q, k, v, window: Optional[int] = None,
                     block: int = BLOCK, interpret: bool = False):
    """softmax(q k^T / sqrt(128), key <= query [and query - key < window])
    v, query head ``h`` reading key-value head ``h // (H / G)``.

    q: (B, T, H*128); k, v: (B, T, G*128). Returns (B, T, H*128). ``T``
    need not be a multiple of ``block``: the rows are padded at the end,
    where causality keeps them out of every real row's sum.
    """
    t = q.shape[1]
    out, _ = _fwd(*(_padded(x, block) for x in (q, k, v)), window, block,
                  interpret)
    return out[:, :t]


def _vjp_fwd(q, k, v, window, block, interpret):
    t = q.shape[1]
    out, stats = _fwd(*(_padded(x, block) for x in (q, k, v)), window,
                      block, interpret)
    # named on the residuals themselves, so that a remat policy can keep
    # them and the backward pass does not run the forward kernel again
    out = checkpoint_name(out[:, :t], "attn_out")
    stats = checkpoint_name(stats, "attn_stats")
    return out, (q, k, v, out, stats)


def _vjp_bwd(window, block, interpret, res, dout):
    q, k, v, out, stats = res
    b, t, width = q.shape
    group = width // k.shape[2]
    # delta = rowsum(do * o), a head: (B, T, G, group) -> lanes of the
    # statistics' layout
    delta = jnp.sum((dout.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(b, t, -1, group, LANES), axis=-1)
    delta = jnp.pad(delta.swapaxes(1, 2),
                    ((0, 0), (0, 0), (0, -t % block), (0, LANES - group)))
    q, k, v, dout = (_padded(x, block) for x in (q, k, v, dout))
    operands = [(q, "q"), (k, "kv"), (v, "kv"), (dout, "q"),
                (stats, "stats"), (delta, "stats")]
    kw = dict(t=q.shape[1], group=group, block=block, window=window,
              interpret=interpret)
    dq_s = pltpu.VMEM((block, group * LANES), jnp.float32)
    if fused_backward_fits(t, group, q.dtype.itemsize, block) is None:
        acc = pltpu.VMEM((q.shape[1], LANES), jnp.float32)
        dq, dk, dv = _call(_causal_bwd_kernel, operands,
                           [(q, "q"), (k, "kv_all"), (v, "kv_all")],
                           [dq_s, acc, acc], key_major=False, **kw)
    else:
        (dq,) = _call(_causal_dq_kernel, operands, [(q, "q")], [dq_s],
                      key_major=False, **kw)
        acc = pltpu.VMEM((block, LANES), jnp.float32)
        dk, dv = _call(_causal_dkv_kernel, operands, [(k, "kv"), (v, "kv")],
                       [acc, acc], key_major=True, **kw)
    return dq[:, :t], dk[:, :t], dv[:, :t]


causal_attention.defvjp(_vjp_fwd, _vjp_bwd)
