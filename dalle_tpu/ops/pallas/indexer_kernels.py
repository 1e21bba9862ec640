"""An indexer's scores, tile by tile, and their gradient under its loss.

An indexer scores every key before a query with a few narrow heads over
ONE key head,

    I[t, s] = scale * sum_j w[t, j] * relu(q[t, j] . k[s])        f32

and the largest ``topk`` scores of a query name the keys its attention
reads (models/sparse_lm.py). :func:`index_scores` makes the (B, T, T)
array of them over the causal band's (block x block) tiles, the band and
the grid being ``causal_attention_kernels``'s (``band_pairs``, ``_call``);
the tiles above the band are never written. Heads of 64 lanes sit two a
lane tile in the projection's own (B, T, J*64) array, and no array is 64
lanes wide: the one key reaches the kernels as the (B, T, 256) placement
``[k, 0 | 0, k]`` (latent attention's shared rotary key's, ``_placed``), so
that ``q_tile k_placed^T`` over 128 lanes is one head's product, the other
head's lanes meeting noughts. The MXU runs 128 deep for the 64 a head
needs.

The indexer learns from a loss of its own, the KL from a target
distribution ``pbar`` over a query's set to the softmax of its scores
there, so its scores' cotangent is ``coef * (softmax_S(I) - pbar)`` on the
set and nothing elsewhere. :func:`index_grads` makes that tile in VMEM
from the selection's own array (``sel``: the score on the set,
``MASK_VALUE`` off it), ``pbar``'s tile and each row's log-sum-exp, and
from it the cotangents of ``q`` (a query block's scratch), ``k`` (one (T,
128) f32 accumulator over all pairs, each head's part in its own half of
the lanes, which the caller adds) and ``w`` (a lane a head): no (T, T)
cotangent exists. Three products a head and tile (the product again, ``dz
k``, ``dz^T q``); the operands' dtype on the MXU, f32 sums.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_tpu.ops.pallas import causal_attention_kernels as kernels
from dalle_tpu.ops.pallas.causal_attention_kernels import (
    _NT, _head, _padded, _padded_sel, _placed, BLOCK, HALF, LANES,
    MASK_VALUE)


def fits(tokens: int, heads: int, head_dim: int, itemsize: int,
         block: int = BLOCK) -> Optional[str]:
    """None where the kernels take an indexer of ``heads`` heads of
    ``head_dim`` lanes over ``tokens`` tokens a sample, else why not."""
    if head_dim != HALF:
        return f"indexer heads of {head_dim} lanes are not two a lane tile"
    if heads % 2 or heads + 2 > LANES:
        return (f"{heads} indexer heads are not pairs with two lanes to "
                f"spare among {LANES}")
    t = tokens + -tokens % block
    tile = block * block * 4
    need = (3 * t * LANES * 4                       # dk: one, and its output
            + (1 + 2 * 2) * block * heads * HALF * 4    # dq: one; q, dq tiles
            + 2 * 2 * tile + 6 * tile)              # sel, pbar; z, r, ds, dz
    if need > kernels.VMEM_LIMIT_BYTES:
        return (f"the key's cotangent over {t} tokens needs "
                f"{need / 2 ** 20:.1f} MiB of VMEM, over "
                f"{kernels.VMEM_LIMIT_BYTES / 2 ** 20:g}")
    return None


def _index_scores_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                         w_ref, o_ref, *, scale: float, group: int,
                         block: int, window: Optional[int]):
    """group: the queries' lane tiles, two heads each."""
    w = w_ref[0]                             # head j's weights in lane j
    total = jnp.zeros((block, block), jnp.float32)
    for j in range(2 * group):
        z = jax.lax.dot_general(q_ref[0, :, _head(j // 2)],
                                k_ref[0, :, _head(j % 2)], _NT,
                                preferred_element_type=jnp.float32)
        total += w[:, j:j + 1] * jnp.maximum(z, 0.0)
    o_ref[0] = total * scale


def _index_grads_kernel(qi_ref, ki_ref, first_ref, last_ref, q_ref, k_ref,
                        rows_ref, sel_ref, pbar_ref, dq_ref, dk_ref, dw_ref,
                        dq_s, dk_s, dw_s, *, scale: float, group: int,
                        block: int, window: Optional[int]):
    """rows: head j's weights in lane j, then the row's log-sum-exp over its
    set and the loss's cotangent for the row."""
    p = pl.program_id(2)
    heads = 2 * group

    @pl.when(p == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)

    @pl.when(first_ref[p] == 1)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)
        dw_s[...] = jnp.zeros(dw_s.shape, jnp.float32)

    rows, sel = rows_ref[0], sel_ref[0]
    lse, coef = rows[:, heads:heads + 1], rows[:, heads + 1:heads + 2]
    # the scores' cotangent (times ``scale``, so that it is the products')
    ds = jnp.where(sel > MASK_VALUE,
                   coef * (jnp.exp(sel - lse) - pbar_ref[0]), 0.0) * scale
    keys = pl.ds(pl.multiple_of(ki_ref[p] * block, block), block)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, LANES), 1)
    dw = jnp.zeros((block, LANES), jnp.float32)
    for c in range(group):
        q = q_ref[0, :, _head(c)]
        dk = []
        for a in range(2):
            j, k = 2 * c + a, k_ref[0, :, _head(a)]
            z = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            dw += jnp.where(lane == j, jnp.sum(
                ds * jnp.maximum(z, 0.0), axis=1, keepdims=True), 0.0)
            dz = jnp.where(z > 0.0, ds * rows[:, j:j + 1], 0.0).astype(
                q.dtype)
            # the key is nought in the other head's half: its lanes stay
            dq_s[:, _head(c)] += jnp.dot(dz, k,
                                         preferred_element_type=jnp.float32)
            dk.append(jnp.dot(dz.T, q, preferred_element_type=jnp.float32))
        # dz^T q is a head's in its own half of the lanes only
        dk_s[keys, :] += jnp.where(lane // HALF == 0, *dk)
    dw_s[...] += dw

    @pl.when(last_ref[p] == 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_s[...]

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_s[...]


def _row_lanes(*parts):
    """(B, T, 128) f32: the parts side by side, noughts after them."""
    rows = jnp.concatenate([x.astype(jnp.float32) for x in parts], axis=2)
    return jnp.pad(rows, ((0, 0), (0, 0), (0, LANES - rows.shape[2])))


def _specs(block: int):
    at = lambda f: (lambda i, j, p, qi, ki, fi, la: f(i, qi[p], ki[p]))
    return {
        "placed": pl.BlockSpec((1, block, 2 * LANES),
                               at(lambda i, qb, kb: (i, kb, 0))),
        "rows": pl.BlockSpec((1, block, LANES),
                             at(lambda i, qb, kb: (i, qb, 0)))}


def index_scores(q, k, w, scale: float, block: int = BLOCK,
                 interpret: bool = False):
    """(B, T', T') f32, T' = T padded to whole blocks: the scores of the
    module docstring in the tiles of the causal band, *unwritten* above it.
    q: (B, T, J*64); k: (B, T, 64); w: (B, T, J). No gradient is defined:
    the loss's reaches q, k and w through :func:`index_grads`."""
    q, k, w = (_padded(x, block) for x in (q, k, w))
    b, t, width = q.shape
    (scores,) = kernels._call(
        _index_scores_kernel,
        [(q, "q"), (_placed(k), "placed"), (_row_lanes(w), "rows")],
        [(jax.ShapeDtypeStruct((b, t, t), jnp.float32), "sel")], [],
        t=t, group=width // LANES, block=block, window=None,
        key_major=False, interpret=interpret, scale=scale, steps=1,
        more_specs=_specs(block))
    return scores


def index_grads(q, k, w, lse, coef, sel, pbar, scale: float,
                block: int = BLOCK, interpret: bool = False):
    """Cotangents of (q, k, w) under the loss whose cotangent on the scores
    is ``coef[t] * (exp(sel[t, s] - lse[t]) - pbar[t, s])`` on a row's set
    and nought off it. lse, coef: (B, T) f32; sel, pbar: (B, T, T) f32."""
    t, heads = q.shape[1], w.shape[2]
    rows = _row_lanes(w, lse[..., None], coef[..., None])
    q, kp, rows = (_padded(x, block) for x in (q, _placed(k), rows))
    sel, pbar = _padded_sel(sel, block), _padded_sel(pbar, block)
    b, padded, width = q.shape
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dq, dk, dw = kernels._call(
        _index_grads_kernel,
        [(q, "q"), (kp, "placed"), (rows, "rows"), (sel, "sel"),
         (pbar, "sel")],
        [(q, "q"), (f32((b, padded, LANES)), "k_rope_all"),
         (f32((b, padded, LANES)), "rows")],
        [pltpu.VMEM((block, width), jnp.float32),
         pltpu.VMEM((padded, LANES), jnp.float32),
         pltpu.VMEM((block, LANES), jnp.float32)],
        t=padded, group=width // LANES, block=block, window=None,
        key_major=False, interpret=interpret, scale=scale, steps=1,
        heads_parallel=False, more_specs=_specs(block))
    dk = (dk[..., :HALF] + dk[..., HALF:]).astype(k.dtype)
    return dq[:, :t], dk[:, :t], dw[:, :t, :heads].astype(w.dtype)
