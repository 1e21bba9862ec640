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

:func:`index_select` chooses a query's keys from that array: ``sel``, the
score where the key is one of the row's ``topk`` largest before it and
``MASK_VALUE`` elsewhere (``sparse_lm.select_keys``' array bit for bit,
``lax.top_k``'s sets). A block of ``SELECT_ROWS`` queries' scores is brought
into VMEM once; the order-preserving integer images of 32 keys a word are
transposed into bit planes there (``GROUP`` keys of a row: a plane is one
lane tile a row), and a row's threshold is found from the top bit down, a
bit a pass, by counting: a pass is an AND, a population count and an add a
word of 32 keys. No sort, nothing approximate. On the v5e at B 1, T 8 192,
2 048 keys a query (PERF.md section 6, PR 53) a call is 0.86 ms, of which
bringing the blocks in and out alone is 0.82; a compare of every key
against its row's candidate a pass took 1.38 ms (a bit a pass; 1.87 at two
bits, three compares a reading), the XLA code 5.3.
"""

from __future__ import annotations

import functools
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
    rows = min(SELECT_ROWS, block)
    # the selection: a row block's scores and its selection, two buffers
    # each; its bit planes and the live keys' words
    need = (2 * 2 * rows * t + 33 * -(-t // GROUP) * rows * LANES) * 4
    if need > kernels.VMEM_LIMIT_BYTES:
        return (f"a row block of {rows} queries' scores over {t} keys, its "
                f"selection and their bit planes need {need / 2 ** 20:.1f} "
                f"MiB of VMEM, over {kernels.VMEM_LIMIT_BYTES / 2 ** 20:g}")
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


SELECT_ROWS = 128                     # queries a block of the selection
SELECT_TURN = 512                     # keys an inner turn of its writes
GROUP = 32 * LANES                    # keys whose bits a plane's words hold
_LEAST = -2 ** 31                     # a signed image's least value


def _image(x):
    """f32 -> int32 whose order is the numbers' (-0.0 counted as 0.0):
    ``sparse_lm._sortable``'s image, signed."""
    bits = pltpu.bitcast(x + 0.0, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _planes(words):
    """(32, ...) int32 words of 32 bits, transposed bit for bit (five rounds
    of swaps between words 16, 8, 4, 2 and 1 apart, each on all 16 pairs at
    once): bit 31 - j of word 31 - b of the result is bit b of word j."""
    rest = words.shape[1:]
    j, m = 16, 0x0000FFFF
    while j:
        pairs = words.reshape(32 // (2 * j), 2, j, *rest)
        low, high = pairs[:, 0], pairs[:, 1]
        # the filled-in high bits of an arithmetic shift fall outside ``m``
        t = (low ^ (high >> j)) & m
        words = jnp.stack([low ^ t, high ^ (t << j)], axis=1).reshape(
            32, *rest)
        j >>= 1
        m ^= (m << j) & 0xFFFFFFFF
    return words


def _index_select_kernel(x_ref, o_ref, planes, alive, *, topk: int, rows: int,
                         turn: int):
    """x_ref, o_ref: a block of ``rows`` queries' scores over every key and
    their selection; planes: (groups * 32, rows, 128) int32, word (r, l) of
    plane 32 g + 31 - b the bit b of the 32 keys 4096 g + 128 j + l of row
    r; alive: (groups, rows, 128), the keys whose higher bits are the
    threshold's."""
    width = x_ref.shape[2]
    r0 = pl.program_id(1) * rows
    last = r0 + rows - 1
    mine = last // turn + 1          # turns with a key at or before ``last``
    groups = last // GROUP + 1
    tiles = turn // LANES
    shape = (rows, LANES)
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    k = jnp.minimum(row[:, :1] + 1, topk)
    wide = lambda x: jnp.broadcast_to(x, shape)

    def over(first, last, body, carry=None):
        """``body(keys, first key, carry)`` over the lane tiles of the
        turns ``first`` .. ``last`` - 1."""
        def a_turn(j, carry):
            for c in range(tiles):
                at = pl.multiple_of(j * turn + c * LANES, LANES)
                carry = body(pl.ds(at, LANES), at, carry)
            return carry
        return jax.lax.fori_loop(first, last, a_turn, carry)

    def off(keys, at, _):
        o_ref[0, :, keys] = jnp.full(shape, MASK_VALUE, jnp.float32)
    over(mine, width // turn, off)

    def write(chosen):
        """The selection up to the block's last query: the score where
        ``chosen(scores, first key)`` of the keys up to the query."""
        def body(keys, at, _):
            x = x_ref[0, :, keys]
            o_ref[0, :, keys] = jnp.where(
                chosen(x, at) & (lane + at <= row), x, MASK_VALUE)
        over(0, mine, body)

    @pl.when(last < topk)
    def _():
        write(lambda x, at: True)            # every key before the query

    @pl.when(last >= topk)
    def _():
        # the images' bits, 32 keys a word, eight rows at a time
        def eight(o, _):
            at_row = pl.multiple_of(o * 8, 8)
            here = pl.ds(at_row, 8)
            for g in range(-(-width // GROUP)):
                @pl.when(g < groups)
                def _():
                    first = g * GROUP
                    held = min(GROUP, width - first)
                    at = lambda axis: jax.lax.broadcasted_iota(
                        jnp.int32, (8, held), axis)
                    # the image as an unsigned number's bits; a key after
                    # its query is nought, the least
                    bits = jnp.where(
                        first + at(1) <= r0 + at_row + at(0),
                        _image(x_ref[0, here, first:first + held]) ^ _LEAST,
                        0)
                    words = [bits[:, j:j + LANES]
                             for j in range(0, held, LANES)]
                    words += [jnp.zeros((8, LANES), jnp.int32)] * (
                        32 - len(words))
                    planes[32 * g:32 * g + 32, here, :] = _planes(
                        jnp.stack(words))
        jax.lax.fori_loop(0, rows // 8, eight, None)

        def each(body, carry=None):
            return jax.lax.fori_loop(0, groups, body, carry)

        def count(words):
            """(rows, 1): the set bits of ``words(g)`` over the groups,
            summed word by word and across lanes once."""
            return jnp.sum(each(
                lambda g, acc: acc + jax.lax.population_count(words(g)),
                jnp.zeros(shape, jnp.int32)), axis=1, keepdims=True)

        def fresh(g, _):
            alive[g] = jnp.full(shape, -1, jnp.int32)
        each(fresh)

        def settle(i, state):
            """The threshold's bit 31 - i: set where the keys above the
            bits found so far and the live keys that hold it are ``k``."""
            above, kth = state
            ones = lambda g: planes[32 * g + i] & alive[g]
            n = count(ones)
            keeps = above + n >= k
            kept = wide(keeps)

            def narrow(g, _):
                held = ones(g)
                alive[g] = jnp.where(kept, held, alive[g] ^ held)
            each(narrow)
            bit = jnp.left_shift(jnp.int32(1), 31 - i)
            return (jnp.where(keeps, above, above + n),
                    jnp.where(keeps, kth | bit, kth))

        nought = jnp.zeros((rows, 1), jnp.int32)
        above, kth = jax.lax.fori_loop(0, 32, settle, (nought, nought))
        tied = count(lambda g: alive[g])
        edge = wide(kth ^ _LEAST)            # the signed image's threshold
        straddles = jnp.max((above + tied - k).astype(jnp.float32)) > 0

        @pl.when(jnp.logical_not(straddles))
        def _():
            write(lambda x, at: _image(x) >= edge)

        # ``lax.top_k``'s tie rule: of the keys at the threshold the lowest
        # by position; a search of its own, only where some row holds more
        # keys at or above its threshold than it may take
        @pl.when(straddles)
        def _():
            want = k - above

            def mark(keys, at, _):           # the output's block as a slate
                o_ref[0, :, keys] = jnp.where(
                    (_image(x_ref[0, :, keys]) == edge)
                    & (lane + at <= row), 1.0, 0.0)
            over(0, mine, mark)

            def place(i, taken):
                """The last tied key a row takes, a bit of its position a
                pass: the largest with fewer than ``want`` before it."""
                candidate = taken + jnp.left_shift(
                    jnp.int32(1), (width - 1).bit_length() - 1 - i)
                before = wide(candidate)
                n = jnp.sum(over(0, mine, lambda keys, at, acc: acc
                                 + jnp.where((o_ref[0, :, keys] > 0.0)
                                             & (lane + at < before), 1, 0),
                                 jnp.zeros(shape, jnp.int32)),
                            axis=1, keepdims=True)
                return jnp.where(n < want, candidate, taken)

            taken = wide(jax.lax.fori_loop(0, (width - 1).bit_length(),
                                           place, nought))

            write(lambda x, at: (_image(x) > edge) | (
                (_image(x) == edge) & (lane + at <= taken)))


def index_select(scores, topk: int, block: int = BLOCK,
                 interpret: bool = False):
    """(B, T', T') f32 ``sel`` of :func:`index_scores`' array: a row's score
    where the key is one of its ``topk`` largest over the keys up to the
    query (every such key where there are no more; ties to the lower key,
    as ``lax.top_k``), ``MASK_VALUE`` elsewhere, every column written.
    ``sparse_lm.select_keys``' sets, bit for bit; nothing above the causal
    band's tiles is searched or counted."""
    b, t, _ = scores.shape
    rows, turn = min(SELECT_ROWS, block), min(SELECT_TURN, block)
    groups = -(-t // GROUP)
    return pl.pallas_call(
        functools.partial(_index_select_kernel, topk=topk, rows=rows,
                          turn=turn),
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((1, rows, t), lambda i, r: (i, r, 0))],
        out_specs=pl.BlockSpec((1, rows, t), lambda i, r: (i, r, 0)),
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((32 * groups, rows, LANES), jnp.int32),
                        pltpu.VMEM((groups, rows, LANES), jnp.int32)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=kernels.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(scores)


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
