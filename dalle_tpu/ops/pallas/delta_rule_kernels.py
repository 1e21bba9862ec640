"""The gated delta rule in chunks as a forward and a backward Mosaic kernel:
a chunk's tables, its triangular inverse and the carried (dk x dv) states
stay in VMEM.

``o_t = S_t^T q_t`` of ``S_t = e^{g_t} S_{t-1} + k_t (beta_t (v_t - (e^{g_t}
S_{t-1})^T k_t))^T`` in chunks of Q tokens, the algorithm of
``models/sparse_lm.chunked_delta_rule`` (its module docstring, "How the rule
runs"): with ``cs`` the running sum of ``g`` inside a chunk, ``D_ij = [j <=
i] e^{cs_i - cs_j}`` and ``A_ij = [j < i] beta_i D_ij (k_i . k_j)``, a chunk
that starts from the state ``S`` writes ``U = T (beta v) - (T (beta e^{cs}
k)) S`` with ``T = (I + A)^-1``, reads out ``o = (e^{cs} q) S + (D o q k^T)
U`` and hands on ``e^{cs_last} S + (e^{cs_last - cs} k)^T U``. As XLA code a
block of 16 chunks makes every table as an HBM array and a ``lax.scan`` of
128 steps a layer does three small batched products a step with a slice in
and an update out around each: 21.2 ms a forward, replay and backward at
``qwen3next80b``'s local shape (1 x 8 192 tokens, 16 key heads x 128 serving
32 value heads x 128, chunks of 64, bfloat16), 0.507 s of a 1.675 s step,
where the arithmetic is 0.023 s of the MXU (PERF.md section 5, PR 64). The
kernels take 7.2 ms (2.1 forward, 2.1 the replay's, 2.9 backward: section 6,
PR 65).

**Packs.** Two heads' (64 x 64) tables are one block-diagonal (128 x 128)
operand (:func:`pack_of`: a product of two (64 x 64) matrices holds the MXU
as long as one of (128 x 128)): a pack's P heads lie one after another on
the sublanes of its (W, .) arrays, W = P Q, and on the lanes of its rows;
what is a head's alone (its state, its ``v``) lies on the head's own lanes,
and a product with a state is one product for the pack with the other
heads' blocks nought (:func:`_diagonal`) or dropped (:func:`_of_diagonal`).

**A grid step** is (sample, a few key heads, a few chunks), the chunks last
and in turn. It loads the chunks' ``q`` and ``k`` (tokens, keys dk), ``v``
(tokens, keys r dv) of the r = H / G value heads a key head, and their ``g``
and ``beta`` as rows (a pack a sublane, its heads' tokens of a chunk on the
lanes: the two small f32 arrays are XLA code around the kernel,
:func:`rule`). ``cs`` is a product of the rows with triangles of ones, the
whole grid step's at once; the rows' columns (a token a sublane) a
transpose a chunk. The work is in two kinds of loop. **What waits for
nothing** (a chunk's tables: ``A``, the inverse, ``u_own = T (beta v)``, ``w
= T (beta e^{cs} k)``, ``D o q k^T``, ``e^{cs} q``, ``(e^{cs_last - cs}
k)^T``) is made for ``TURNS`` packs at once, the packs' products written
turn by turn, into VMEM scratch: the MXU takes its products in the program's
order, and a chain of products that each wait for the last (the inverse is
nine deep) leaves it idle unless another chain's stand between (one pack a
turn 5.9 ms a forward call, eight 2.1: section 6, PR 65). **What waits for
the state** is then three products a chunk and pack (``[w ; e^{cs} q] S``,
``k_out^T U``, ``(D o q k^T) U``), the key heads' walks turn by turn. The r
heads' states of a step's key heads are one f32 scratch (dk, keys r dv).

**The inverse** is ``unit_lower_inverse``'s scheme on whole (W x W)
operands: the diagonal blocks of 8 by the finite product ``(I + P)(I +
P^2)(I + P^4)`` of the block-diagonal part ``P`` of ``-A`` (block-diagonal
matrices multiply block by block), then block sizes doubled by substitution
up to the chunk, ``T <- T - T (L T)`` with ``L`` the blocks of ``A`` under
the first of each pair (``T`` is block diagonal, so the product is ``-T22
L21 T11`` where it belongs and nought elsewhere): ten products a pack and
chunk, no loop over blocks and no whole-chunk Neumann product. It is two
thirds of the forward call (1.4 of 2.1 ms), at what the MXU gives thirty
(128 x 128 x 128) products a chunk.

The forward writes ``o`` and, where a gradient will ask (:func:`_core`'s
``custom_vjp`` rule), **the state each grid step starts from**, (B, G /
keys, steps, dk, keys r dv) f32, and **every chunk's inverses in f32**, the
heads' (Q x Q) blocks side by side: 64 MiB each a layer at the cell's sizes
(8 key heads and 4 chunks a step), where a state a chunk would be 256 MiB;
the inverse kept is what the XLA lowering's ``custom_vjp`` keeps too, and
making it a third time cost 1.3 ms a call. **Across a rematerialised
layer's replay** the rule keeps what it made and what only its scope makes:
the derivative's forward names, with
``jax.ad_checkpoint.checkpoint_name`` and on the residuals themselves as the
attention kernels name their context and statistics, ``o``, the states and
the inverses (``KEPT_MADE``), and the normalised ``q`` and ``k`` and the
``g`` and ``beta`` rows (``KEPT_READ``), and
``sparse_lm.KEPT_OF_A_LAYER`` holds the names: :func:`kept_bytes`, 258 MiB a
sample and layer at the cell's sizes (64 + 64 + 64, 32 + 32, 1 + 1), alive
from the layer's forward to its backward, and the backward pass runs no
forward kernel and none of the L2 norms' and the rows' XLA code a second
time. A policy without the names replays both (the forward call 2.07 ms a
layer and micro-step at the cell's shape, which JAX differentiates through
the same ``keep`` call in the first forward too; the XLA code 0.6 ms:
PERF.md section 6, PR 66). The XLA lowering
(``sparse_lm.chunked_delta_rule``) is not part of this: it keeps
its own ``jax.checkpoint`` a block. The backward's grid step
makes its chunks' tables from the inverses, walks the chunks forward from
the step's state (keeping in VMEM the state each chunk starts from and
``U``), then in reverse with the states' cotangent in scratch (keeping it a
chunk, and ``dU``), and then makes everything else, again ``TURNS`` packs at
once; the steps in reverse. It writes ``dq`` and ``dk`` (summed over the r
heads in VMEM), ``dv``, and the cotangents of the ``g`` and ``beta`` rows.
The reverse walk keeps its tables transposed (``D^T``, ``(q k^T)^T = k
q^T``, ``dT^T``, ``dA^T``): then every product with a transposed left
operand is a plain one (``attn^T do``, ``q_in^T do``, ``w^T du`` with ``w^T
= (beta e^{cs} k)^T T^T`` made from ``k^T`` and the rows), the inverse's
derivative ``dA = -T^T dT T^T`` is ``dA^T = -(T dT^T) T`` with the inverse as
it stands, and what a table gives ``beta`` and ``g`` is a sum down its
columns, which lands on the rows' layout. A token's decay ``g_m`` scales
every pair (i, l) of a chunk with l < m <= i, so the tables' share of its
cotangent is the sum of ``G = dD o D`` over that block: ``G^T`` times a
triangle of ones, then a masked sum down the columns, taken of one rounding
of the table (not a row sum less a column sum: PERF.md section 6, PR 58).
What the (tokens, d) operands give the rows (through ``beta``, ``e^{cs}``,
``e^{cs_last - cs}`` and the chunk's whole decay) are sums over a head's
lanes, turned to rows by one transpose a chunk, and one reverse running sum
a grid step. The few transposes left of ``x.dtype`` arrays (the (W x W)
cotangents of ``k k^T`` and ``q k^T``, ``T^T``, ``q^T`` and ``k^T``) are
products with the identity, exact in ``x.dtype``.

Numerics are ``chunked_delta_rule``'s, to the letter of
``benchmark/configs/qwen3next80b.json``'s ``tolerance.reason``: ``g``,
``beta``, the decays, their running sums and the carried states in f32; the
inverse's own products in f32 from the bfloat16 pieces of
``Precision.HIGH`` (:func:`_mm3`: Mosaic takes no such precision, so the
pieces are made here), both ways; every other product with ``x.dtype``
operands and f32 accumulation, the inverse rounded to ``x.dtype`` once made
and the state where it enters a product; cotangents enter the MXU in
``x.dtype`` as a default-precision product of an f32 cotangent does. The
sums that are products with noughts and ones take an f32 operand whole (the
highest precision: the rows' running sums, once a grid step) or as two
bfloat16 pieces (the tables' sum for ``g``). On the v5e at the cell's shape
every result lies as near the same expression in f32 as the XLA code's
(``o`` 0.0034 / 0.0034, ``dq`` 0.0034 / 0.0038, ``dk`` 0.0034 / 0.0039,
``dv`` 0.0030 / 0.0031, ``dg`` 0.0027 / 0.0032, ``dbeta`` 0.0025 / 0.0026
with ``A`` = 0.05: section 6, PR 65).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_tpu.ops.pallas.ssm_scan_kernels import (LANES, SUBLANES, _HIGHEST,
                                                   _eye, _nn, _nt,
                                                   _ones_where, _rows,
                                                   _summed, _transposed)

# the scope the calls open again inside their jit, so that a trace reads
# ``rule[mosaic]`` under the caller's ``gdn/rule`` (head_norm_kernels.SCOPE)
SCOPE = "rule"
# the inverse's diagonal blocks made by the finite product
# (sparse_lm.INVERSE_BASE)
INVERSE_BASE = 8
# tokens a grid step: what bounds the backward's scratch (the states its
# chunks start from, 128 KiB a chunk at dk = 128, r dv = 256) and how many
# states the forward writes for it (one a step)
STEP_TOKENS = 1024
# key heads a grid step (:func:`keys_a_step`)
KEYS_A_STEP = 8
# packs of heads whose tables are made together, their products turn by
# turn: a grid step's key heads', and as many chunks as make up the number.
# On the v5e at the cell's shape (forward / with the replay and the
# backward, ms a call): one pack a turn 5.88 / 21.8, two 3.88 / 14.8, four
# 3.19 / 12.4, eight 3.10 / 12.1 with one key head a step; eight as 4 key
# heads x 2 chunks 2.26 / 7.57, as 8 x 1 2.13 / 7.18, sixteen 2.04 / 6.71 at
# twice the seconds to compile (PERF.md section 6, PR 65)
TURNS = 8
_VMEM = 64 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM)
# the names (``jax.ad_checkpoint.checkpoint_name``) of what a
# rematerialisation policy may keep of a derivative's forward: what the
# kernel made (``o``, the state each grid step starts from, every chunk's
# inverses) and what it read that only the caller's scope makes (the
# normalised ``q`` and ``k``, the two rows)
KEPT = KEPT_MADE, KEPT_READ = "rule_made", "rule_read"
# how the backward gets its states, a fact of the site's record
BACKWARD = ("one kernel, a grid step's chunks forward again from the state "
            "the forward kept a step, then in reverse")


def keys_a_step(key_heads: int, heads_a_key: int, chunk: int) -> int:
    """Key heads a grid step, whose walks over the chunks (each waits for
    its own last product) are written turn by turn: the most that divide
    ``key_heads``, up to ``KEYS_A_STEP``, whose packs' rows are a tile's."""
    packs = heads_a_key // pack_of(heads_a_key, chunk)
    return max(n for n in range(1, KEYS_A_STEP + 1)
               if key_heads % n == 0 and (n == 1 or 3 * _rows(n * packs)
                                          <= LANES))


def vmem_bytes(chunk: int, heads_a_key: int, dk: int, dv: int,
               itemsize: int, chunks: int, keys: int = 1) -> int:
    """What the backward's blocks (the larger kernel's) hold in VMEM at
    ``chunks`` chunks and ``keys`` key heads a grid step, the pipeline's
    two buffers an operand, and its scratch."""
    pack = pack_of(heads_a_key, chunk)
    packs, w = keys * heads_a_key // pack, pack * chunk
    tokens, lanes = chunks * chunk, keys * heads_a_key * dv
    rows = chunks * _rows(packs) * w * 4
    blocks = (3 * tokens * lanes * itemsize             # v, do, dv
              + 4 * tokens * keys * dk * itemsize       # q, k, dq, dk
              + 4 * rows                                # g, beta, dg, dbeta
              + dk * lanes * 4                          # the step's state
              + chunks * packs * chunk * w * 4)         # the inverses
    a_pack = (w * dv * 4                                # u_own
              + (2 * w * dv + w * w + 5 * w * dk) * itemsize)
    scratch = (2 * (chunks + 1) * dk * lanes * 4        # S, dS, one a chunk
               + 3 * rows + chunks * _rows(packs) * pack * dv * 4
               + chunks * packs * a_pack)
    temporaries = max(TURNS, packs) * 32 * w * max(w, LANES) * 4 \
        + 8 * dk * pack * dv * 4
    return 2 * blocks + scratch + temporaries


def chunks_a_step(chunks: int, chunk: int, heads_a_key: int, dk: int,
                  dv: int, itemsize: int, keys: int = 1) -> int:
    """Chunks a grid step: the most that divide a sample's ``chunks``, keep
    a step within ``STEP_TOKENS`` tokens and its blocks within VMEM."""
    return max(k for k in range(1, max(1, STEP_TOKENS // chunk) + 1)
               if chunks % k == 0 and (k == 1 or vmem_bytes(
                   chunk, heads_a_key, dk, dv, itemsize, k, keys) <= _VMEM))


def kept_bytes(tokens: int, key_heads: int, heads: int, dk: int, dv: int,
               chunk: int, itemsize: int) -> int:
    """Bytes a sample of what the names ``KEPT`` keep of one call: ``o``,
    the state each grid step starts from and every chunk's inverses (f32),
    ``q`` and ``k``, and the ``g`` and ``beta`` rows (f32, :func:`rule`)."""
    r = heads // key_heads
    keys, pack = keys_a_step(key_heads, r, chunk), pack_of(r, chunk)
    chunks = tokens // chunk
    steps = chunks // chunks_a_step(chunks, chunk, r, dk, dv, itemsize, keys)
    rows = key_heads // keys * chunks * _rows(keys * r // pack) * pack * chunk
    return (tokens * heads * dv * itemsize + steps * heads * dk * dv * 4
            + tokens * heads * chunk * 4
            + 2 * tokens * key_heads * dk * itemsize + 2 * rows * 4)


def fits(tokens: int, key_heads: int, heads: int, dk: int, dv: int,
         chunk: int, itemsize: int) -> Optional[str]:
    """None where the kernels take samples of ``tokens`` tokens of
    ``key_heads`` query/key heads of ``dk`` lanes serving ``heads`` value
    heads of ``dv``, in chunks of ``chunk``; else why not."""
    if chunk < SUBLANES or chunk & (chunk - 1):
        return (f"a chunk of {chunk} is no power of two of at least a "
                f"sublane tile of {SUBLANES}")
    if tokens % chunk:
        return f"{tokens} tokens are not whole chunks of {chunk}"
    if dk % LANES or dv % LANES:
        return (f"heads of {dk} and {dv} lanes are not whole {LANES}-lane "
                "tiles")
    if heads % key_heads:
        return (f"{heads} value heads are no whole multiple of {key_heads} "
                "key heads")
    r = heads // key_heads
    if chunk > LANES:
        return f"a chunk of {chunk} passes a lane tile of {LANES}"
    if 3 * _rows(r // pack_of(r, chunk)) > LANES:
        return (f"{r} value heads a key head pass {LANES // 3} rows of a "
                "tile")
    need = vmem_bytes(chunk, r, dk, dv, itemsize, 1,
                      keys_a_step(key_heads, r, chunk))
    if need > _VMEM:
        return (f"a chunk of {chunk} x {r * dv} and a state of {dk} x "
                f"{r * dv} need {need} bytes of VMEM")
    return None


# ---------------------------------------------------------------------------
# Products, and the sums and transposes that are products
# ---------------------------------------------------------------------------

def _iotas(n: int, m: Optional[int] = None):
    """(sublane index, lane index) of an (n, m) array."""
    shape = (n, n if m is None else m)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _pieces(x):
    """An f32 array as the two bfloat16 pieces of ``Precision.HIGH``."""
    first = x.astype(jnp.bfloat16)
    return first, (x - first.astype(jnp.float32)).astype(jnp.bfloat16)


def _mm3(a, b, whole: bool):
    """``a @ b`` of f32 operands at ``sparse_lm._INVERSE_PRECISION``: the
    three bfloat16 products of ``Precision.HIGH`` (about 2^-17 a product),
    or the highest precision where the model computes in f32 (``whole``)."""
    if whole:
        return _nn(a, b, _HIGHEST)
    a0, a1 = _pieces(a)
    b0, b1 = _pieces(b)
    return _nn(a0, b0) + (_nn(a0, b1) + _nn(a1, b0))


def _all(x):
    """The sum of a 2-D array, (1, 1)."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _segments(n: int, m: int, chunk: int):
    """(sublane index, lane index, whether both lie in one head's ``chunk``
    entries) of an (n, m) array whose two axes hold a pack's heads one
    after another."""
    i, j = _iotas(n, m)
    shift = chunk.bit_length() - 1
    return i, j, (i >> shift) == (j >> shift)


def _lane_sums(a, b):
    """The sum over the lanes of ``a`` plus that of ``b``, (rows, 1): one
    reduction where they are as wide as each other (dk = dv)."""
    if a.shape == b.shape:
        return jnp.sum(a + b, axis=1, keepdims=True)
    return jnp.sum(a, axis=1, keepdims=True) \
        + jnp.sum(b, axis=1, keepdims=True)


def _sums_of_a_step(rows_ref, cs_ref, chunk: int, last_ref=None,
                    total_ref=None, reverse: bool = False):
    """The running sums of a grid step's (k, R, W) f32 rows at once, along
    the lanes inside each head's ``chunk`` of them (from a chunk's last
    token back where ``reverse``), into ``cs_ref``: a product with
    triangles of ones at the highest precision, which sums the f32 numbers
    themselves. Into ``last_ref`` (k, R, W) a head's whole sum on every
    lane of its own (``cs_last``: Mosaic broadcasts no single number over
    lanes and sublanes at once), into ``total_ref`` (k, R, P dv) the same on
    the lanes of the head's state."""
    k, n_rows, w = cs_ref.shape
    l, i, same = _segments(w, w, chunk)
    ones = _ones_where(same & ((l >= i) if reverse else (l <= i)),
                       jnp.float32)
    rows = rows_ref[...].reshape(k * n_rows, w)
    cs_ref[...] = _nn(rows, ones, _HIGHEST).reshape(k, n_rows, w)
    if last_ref is not None:
        last_ref[...] = _nn(rows, _ones_where(same, jnp.float32),
                            _HIGHEST).reshape(k, n_rows, w)
    if total_ref is not None:
        wide = total_ref.shape[2]
        l, m = _iotas(w, wide)
        of = _ones_where(l // chunk == m // (wide // (w // chunk)),
                         jnp.float32)
        total_ref[...] = jnp.exp(_nn(rows, of, _HIGHEST)).reshape(
            k, n_rows, wide)


def _columns(rows):
    """(R, W) f32 rows (a pack a sublane, its heads' tokens on the lanes)
    as (W, 128) columns (tokens on the sublanes, row j on lane j): a product
    with the identity that moves the numbers whole."""
    r, w = rows.shape
    if r < LANES:
        rows = jnp.concatenate(
            [rows, jnp.zeros((LANES - r, w), rows.dtype)], axis=0)
    if w == LANES:          # a whole tile: the XLU's, beside the MXU's work
        return rows.T
    return _nt(_eye(w, jnp.float32), rows, _HIGHEST)


def _as_rows(cols):
    """The way back: (W, 128) f32 columns as (128, W) rows."""
    if cols.shape[0] == LANES:
        return cols.T
    return _nt(_eye(LANES, jnp.float32), cols, _HIGHEST)


def _unit_lower_inverses(matrices, chunk: int, whole: bool):
    """``(I + a)^-1`` of each strictly lower-triangular (W, W) f32 ``a``
    whose diagonal blocks of ``chunk`` are a pack's heads':
    ``sparse_lm.unit_lower_inverse``'s scheme on whole operands (module
    docstring), the same products in the same order a head. The matrices'
    products are written turn by turn: the MXU takes its products in the
    program's order, and a product that waits for the one before it waits
    for the whole pipeline (PERF.md section 6, PR 65)."""
    w = matrices[0].shape[0]
    base = min(INVERSE_BASE, chunk)
    i, j = _iotas(w)
    eye = _ones_where(i == j, jnp.float32)
    shift = base.bit_length() - 1
    powers = [jnp.where((i >> shift) == (j >> shift), -a, 0.0)
              for a in matrices]
    inverses = [eye + p for p in powers]
    for _ in range(max(base.bit_length() - 2, 0)):
        powers = [_mm3(p, p, whole) for p in powers]
        inverses = [_mm3(t, eye + p, whole)
                    for t, p in zip(inverses, powers)]
    size = base
    while size < chunk:
        shift = size.bit_length()          # blocks of 2 * size
        under = ((i >> shift) == (j >> shift)) & ((i & size) != 0) \
            & ((j & size) == 0)
        low = [_mm3(jnp.where(under, a, 0.0), t, whole)
               for a, t in zip(matrices, inverses)]
        inverses = [t - _mm3(t, x, whole) for t, x in zip(inverses, low)]
        size *= 2
    return inverses


# ---------------------------------------------------------------------------
# What both kernels make of a chunk
# ---------------------------------------------------------------------------

def pack_of(heads_a_group: int, chunk: int) -> int:
    """Heads whose (chunk x chunk) tables are one block-diagonal (W x W)
    operand, W = heads x chunk: as many as divide the group's and fill a
    lane tile (two of 64 tokens: a product of two (64 x 64) matrices takes
    the MXU as long as one of (128 x 128))."""
    return max(p for p in range(1, heads_a_group + 1)
               if heads_a_group % p == 0 and (p == 1 or p * chunk <= LANES))


def _columns_of(cs_ref, beta_ref, last_ref, at):
    """The (W, 128) columns of a chunk's rows of ``cs``, ``beta`` and
    ``cs_last``: pack p's on lanes p, R + p and 2 R + p."""
    return _columns(jnp.concatenate(
        [cs_ref[at], beta_ref[at], last_ref[at]], axis=0))


def _chunks_of(q_ref, k_ref, cs_ref, beta_ref, last_ref, first, by: int,
               packs: int, pack: int, chunk: int, dk: int):
    """The packs of ``by`` chunks from ``first``, a chunk after another."""
    a_key = packs * dk // q_ref.shape[1]
    chunks = []
    for at in (first + n for n in range(by)):
        cols = _columns_of(cs_ref, beta_ref, last_ref, at)
        chunks += [_Chunk(q_ref, k_ref, cs_ref, beta_ref, last_ref, cols, at,
                          p, pack, chunk, p // a_key, dk)
                   for p in range(packs)]
    return chunks


def _stacked(ref, tokens, p: int, pack: int, width: int):
    """Pack p's heads' (Q, width) blocks of ``ref``'s chunk, one after
    another on the sublanes: (W, width)."""
    return jnp.concatenate(
        [ref[tokens, (p * pack + h) * width:(p * pack + h + 1) * width]
         for h in range(pack)], axis=0)


class _Chunk:
    """A chunk's operands for pack ``p`` of a grid step's, P heads of key
    head ``key``, the heads one after another on the sublanes (W = P Q
    rows): ``q``, ``k`` (W, dk) (the key head's, P times), ``v`` (W, dv)
    where asked; the pack's rows (1, W) and columns (W, 1) of ``cs``,
    ``beta`` and ``cs_last``, the columns out of ``cols``
    (:func:`_columns_of`)."""

    def __init__(self, q_ref, k_ref, cs_ref, beta_ref, last_ref, cols, at,
                 p: int, pack: int, chunk: int, key: int, dk: int):
        n_rows = cs_ref.shape[1]
        self.tokens = pl.ds(pl.multiple_of(at * chunk, chunk), chunk)
        self.at, self.p, self.pack, self.chunk = at, p, pack, chunk
        mine = slice(key * dk, (key + 1) * dk)
        qm, km = q_ref[self.tokens, mine], k_ref[self.tokens, mine]
        self.q = jnp.concatenate([qm] * pack, axis=0) if pack > 1 else qm
        self.k = jnp.concatenate([km] * pack, axis=0) if pack > 1 else km
        self.cs_row = cs_ref[at, p:p + 1]
        self.beta_row = beta_ref[at, p:p + 1]
        self.last_row = last_ref[at, p:p + 1]
        self.cs_col = cols[:, p:p + 1]
        self.beta_col = cols[:, n_rows + p:n_rows + p + 1]
        self.last_col = cols[:, 2 * n_rows + p:2 * n_rows + p + 1]

    def stacked(self, ref, width: int):
        return _stacked(ref, self.tokens, self.p, self.pack, width)


def _lanes(p: int, pack: int, width: int):
    """Pack p's lanes of an array that holds ``width`` a head."""
    return slice(p * pack * width, (p + 1) * pack * width)


def _diagonal(x, pack: int):
    """(W, width) ``x`` of a pack's heads one after another as (W, P
    width): head h's rows on its own lanes, noughts elsewhere."""
    if pack == 1:
        return x
    row, _ = _iotas(*x.shape)
    chunk = x.shape[0] // pack
    return jnp.concatenate(
        [jnp.where((row >= h * chunk) & (row < (h + 1) * chunk), x,
                   jnp.zeros_like(x)) for h in range(pack)], axis=1)


def _of_diagonal(x, pack: int):
    """The way back: head h's rows of its own lanes of (W, P width)."""
    if pack == 1:
        return x
    q, width = x.shape[0] // pack, x.shape[1] // pack
    return jnp.concatenate(
        [x[h * q:(h + 1) * q, h * width:(h + 1) * width]
         for h in range(pack)], axis=0)


def _compact(inverse, pack: int):
    """A pack's block-diagonal (W, W) inverse as (Q, W): the heads' blocks
    side by side (the sum of its row blocks: noughts lie between)."""
    q = inverse.shape[0] // pack
    return sum(inverse[h * q:(h + 1) * q] for h in range(pack))


def _expanded(compact, pack: int):
    """The way back: (Q, W) as the block-diagonal (W, W)."""
    if pack == 1:
        return compact
    q, w = compact.shape
    _, _, same = _segments(w, w, q)
    return jnp.where(same, jnp.concatenate([compact] * pack, axis=0), 0.0)


def _forward_tables(c: _Chunk, inverse, eye, dtype):
    """What a chunk's forward makes of the inverse: ``u_own`` (W, dv) f32,
    ``w`` (W, dk), ``k_out^T`` (dk, W)."""
    f32 = jnp.float32
    inv = inverse.astype(dtype)
    e_col = jnp.exp(c.cs_col)
    v_in = (c.v.astype(f32) * c.beta_col).astype(dtype)
    k_in = (c.k.astype(f32) * (c.beta_col * e_col)).astype(dtype)
    k_out_t = (_transposed(c.k, eye).astype(f32)
               * jnp.exp(c.last_row - c.cs_row)).astype(dtype)
    return _nn(inv, v_in), _nn(inv, k_in).astype(dtype), k_out_t


def _to_invert(c: _Chunk):
    """(``A``, the decays' table), each (W, W) f32, i on the sublanes;
    noughts between the pack's heads."""
    i, j, same = _segments(c.k.shape[0], c.k.shape[0], c.chunk)
    decay = jnp.exp(jnp.where(same & (j <= i), c.cs_col - c.cs_row,
                              -jnp.inf))
    return jnp.where(same & (j < i), c.beta_col * decay * _nt(c.k, c.k),
                     0.0), decay


def _groups(n: int, packs: int):
    """(iterations, chunks an iteration) of the loops whose chunks do not
    wait for each other: ``TURNS`` packs an iteration where they divide."""
    by = max(m for m in range(1, max(1, TURNS // packs) + 1) if n % m == 0)
    return n // by, by


def _delta_rule_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                           *rest, r, chunk, keys, keep):
    """A grid step's chunks: their tables, ``TURNS`` packs at a time,
    then the states in turn. q, k: (k Q, keys dk); v, o: (k Q, keys r dv);
    g, beta: (k, R, W) rows; where ``keep``, start: (dk, keys r dv), the
    state the step
    starts from, and inverses: (k, packs, Q, W) f32, a chunk's heads'
    inverses side by side; scratch: the heads' states side by side (dk,
    keys r dv), the step's ``cs``, ``cs_last`` and whole decays
    (:func:`_sums_of_a_step`),
    and a pack's ``u_own`` (k, packs, W, dv) f32, ``[w ; q_in]`` (k, packs,
    2 W, dk), ``D o q k^T`` (k, packs, W, W) and ``k_out^T`` (k, packs, dk,
    W)."""
    start_ref, inverses_ref = rest[:2] if keep else (None, None)
    (s_ref, cs_ref, last_ref, total_ref, uo_ref, wq_ref, attn_ref,
     koutt_ref) = rest[-8:]
    f32, dtype = jnp.float32, v_ref.dtype
    dk = q_ref.shape[1] // keys
    dv = v_ref.shape[1] // (keys * r)
    k = cs_ref.shape[0]
    pack = pack_of(r, chunk)
    a_key, w = r // pack, pack * chunk
    packs = keys * a_key
    eye = _eye(dk, dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if keep:
        start_ref[...] = s_ref[...]
    _sums_of_a_step(g_ref, cs_ref, chunk, last_ref, total_ref)
    iterations, by = _groups(k, packs)

    def tables(it, carry):
        chunks = _chunks_of(q_ref, k_ref, cs_ref, beta_ref, last_ref,
                            it * by, by, packs, pack, chunk, dk)
        made = [_to_invert(c) for c in chunks]
        inverses = _unit_lower_inverses([a for a, _ in made], chunk,
                                        dtype == f32)
        for c, (_, decay), inverse in zip(chunks, made, inverses):
            c.v = c.stacked(v_ref, dv)
            u_own, w_, k_out_t = _forward_tables(c, inverse, eye, dtype)
            q_in = (c.q.astype(f32) * jnp.exp(c.cs_col)).astype(dtype)
            if keep:
                inverses_ref[c.at, c.p] = _compact(inverse, pack)
            uo_ref[c.at, c.p] = u_own
            wq_ref[c.at, c.p] = jnp.concatenate([w_, q_in], axis=0)
            attn_ref[c.at, c.p] = (decay * _nt(c.q, c.k)).astype(dtype)
            koutt_ref[c.at, c.p] = k_out_t
        return carry

    jax.lax.fori_loop(0, iterations, tables, None)

    def one(at, carry):
        # the packs' walks turn by turn, the state's products first
        tokens = pl.ds(pl.multiple_of(at * chunk, chunk), chunk)
        lanes = [_lanes(p, pack, dv) for p in range(packs)]
        states = [s_ref[:, at_] for at_ in lanes]
        both = [_nn(wq_ref[at, p], states[p].astype(dtype))  # (2 W, P dv)
                for p in range(packs)]
        us = [(uo_ref[at, p] - _of_diagonal(both[p][:w], pack)).astype(dtype)
              for p in range(packs)]
        for p in range(packs):
            s_ref[:, lanes[p]] = total_ref[at, p:p + 1] * states[p] \
                + _nn(koutt_ref[at, p], _diagonal(us[p], pack))
        for p in range(packs):
            o = _of_diagonal(both[p][w:], pack) + _nn(attn_ref[at, p], us[p])
            for h in range(pack):
                head = p * pack + h
                o_ref[tokens, head * dv:(head + 1) * dv] = \
                    o[h * chunk:(h + 1) * chunk].astype(dtype)
        return carry

    jax.lax.fori_loop(0, k, one, None)


def _delta_rule_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref,
                           inverses_ref, do_ref, dq_ref, dk_ref, dv_ref,
                           dg_ref, dbeta_ref, s_ref, cs_ref, last_ref,
                           total_ref, uo_ref, w_ref, koutt_ref, ds_ref,
                           form_ref, states_ref, dstates_ref, u_ref, du_ref,
                           attn_ref, kout_ref, qin_ref, wt_ref, *, r, chunk,
                           keys):
    """A grid step's chunks: their tables, then forward from the state the
    forward kept, then in reverse for the states' cotangent, then
    everything else, ``TURNS`` packs at a time; the steps in reverse (the
    index maps). Operands as the forward's, with the inverses it kept and
    ``do`` like ``o``; dq, dk, dv, dg, dbeta like q, k, v, g, beta.
    Scratch: the forward's first seven (with ``w`` alone, (k, packs, W,
    dk)), the cotangent of the state a chunk leaves behind, what the tables
    give the step's ``g`` rows; the state each chunk starts from and the
    cotangent of the state it leaves (k, dk, keys r dv each); a pack's
    ``U`` and ``dU`` (k, packs, W, dv), ``(D o q k^T)^T`` (k, packs, W, W),
    ``k_out`` (k, packs, W, dk), ``q_in^T`` and ``w^T`` (k, packs, dk,
    W)."""
    f32, dtype = jnp.float32, v_ref.dtype
    whole = dtype == f32
    dk = q_ref.shape[1] // keys
    dv = v_ref.shape[1] // (keys * r)
    k, n_rows, _ = cs_ref.shape
    pack = pack_of(r, chunk)
    a_key, w = r // pack, pack * chunk
    packs = keys * a_key
    eye, eye_w = _eye(dk, dtype), _eye(w, dtype)
    sub, lane, same = _segments(w, w, chunk)     # a (W, W) table's indices
    upper, before = same & (sub <= lane), same & (sub < lane)
    # G^T times [i >= m], then the sum over l < m down the columns
    from_on = _ones_where(same & (sub >= lane), dtype)
    col_sub, col_lane = _iotas(w, LANES)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    s_ref[...] = start_ref[...]
    form_ref[...] = jnp.zeros_like(form_ref)
    _sums_of_a_step(g_ref, cs_ref, chunk, last_ref, total_ref)
    iterations, by = _groups(k, packs)

    def chunks_of(it):
        return _chunks_of(q_ref, k_ref, cs_ref, beta_ref, last_ref,
                          it * by, by, packs, pack, chunk, dk)

    def decays_t(c):
        """``[j <= i] exp(cs_i - cs_j)``, j on the sublanes (every entry
        the same difference of the same two numbers as the forward's)."""
        return jnp.exp(jnp.where(upper, c.cs_row - c.cs_col, -jnp.inf))

    def tables(it, carry):
        for c in chunks_of(it):
            c.v = c.stacked(v_ref, dv)
            at, p = c.at, c.p
            inverse = _expanded(inverses_ref[at, p], pack)
            uo_ref[at, p], w_ref[at, p], koutt_ref[at, p] = _forward_tables(
                c, inverse, eye, dtype)
            kf = c.k.astype(f32)
            e_row = jnp.exp(c.cs_row)
            attn_ref[at, p] = (decays_t(c) * _nt(c.k, c.q)).astype(dtype)
            kout_ref[at, p] = (kf * jnp.exp(c.last_col - c.cs_col)
                               ).astype(dtype)
            qin_ref[at, p] = (_transposed(c.q, eye).astype(f32) * e_row
                              ).astype(dtype)
            k_in_t = (_transposed(c.k, eye).astype(f32)
                      * (c.beta_row * e_row)).astype(dtype)
            wt_ref[at, p] = _nt(k_in_t, inverse.astype(dtype)).astype(dtype)
        return carry

    jax.lax.fori_loop(0, iterations, tables, None)

    def forward(at, carry):
        states_ref[at] = s_ref[...]
        lanes = [_lanes(p, pack, dv) for p in range(packs)]
        states = [s_ref[:, at_] for at_ in lanes]
        taken = [_nn(w_ref[at, p], states[p].astype(dtype))
                 for p in range(packs)]
        for p in range(packs):
            u = (uo_ref[at, p] - _of_diagonal(taken[p], pack)).astype(dtype)
            u_ref[at, p] = u
            s_ref[:, lanes[p]] = total_ref[at, p:p + 1] * states[p] \
                + _nn(koutt_ref[at, p], _diagonal(u, pack))
        return carry

    jax.lax.fori_loop(0, k, forward, None)

    def back(step, carry):
        # o = q_in s + attn u; S' = total S + k_out^T u; u = u_own - w s
        at = k - 1 - step
        dstates_ref[at] = ds_ref[...]
        tokens = pl.ds(pl.multiple_of(at * chunk, chunk), chunk)
        lanes = [_lanes(p, pack, dv) for p in range(packs)]
        dos = [_stacked(do_ref, tokens, p, pack, dv) for p in range(packs)]
        dstates = [ds_ref[:, at_] for at_ in lanes]
        through = [_nn(kout_ref[at, p], dstates[p].astype(dtype))
                   for p in range(packs)]
        for p in range(packs):
            du = (_nn(attn_ref[at, p], dos[p])
                  + _of_diagonal(through[p], pack)).astype(dtype)
            du_ref[at, p] = du
            ds_ref[:, lanes[p]] = total_ref[at, p:p + 1] * dstates[p] \
                + _nn(qin_ref[at, p], _diagonal(dos[p], pack)) \
                - _nn(wt_ref[at, p], _diagonal(du, pack))
        return carry

    jax.lax.fori_loop(0, k, back, None)

    def rest(it, carry):
        chunks = chunks_of(it)
        # u_own = T v_in, w = T k_in: dT^T, and through the inverse
        for c in chunks:
            at, p = c.at, c.p
            c.v = c.stacked(v_ref, dv)
            c.do = c.stacked(do_ref, dv)
            lanes = _lanes(p, pack, dv)
            c.state, c.dstate = states_ref[at, :, lanes], \
                dstates_ref[at, :, lanes]
            s = c.state.astype(dtype)
            c.u, c.du = u_ref[at, p], du_ref[at, p]
            c.e_col = jnp.exp(c.cs_col)
            c.vf, c.kf = c.v.astype(f32), c.k.astype(f32)
            c.v_in = (c.vf * c.beta_col).astype(dtype)
            c.k_in = (c.kf * (c.beta_col * c.e_col)).astype(dtype)
            c.dw = (-_nt(_diagonal(c.du, pack), s)).astype(dtype)  # (W, dk)
            c.dq_in = _nt(_diagonal(c.do, pack), s)               # (W, dk)
            c.dk_out = _nt(_diagonal(c.u, pack),
                           c.dstate.astype(dtype))                 # (W, dk)
            c.inverse = _expanded(inverses_ref[at, p], pack)
            c.dt_t = jnp.where(same, _nt(c.v_in, c.du) + _nt(c.k_in, c.dw),
                               0.0).astype(dtype).astype(f32)
        firsts = [_mm3(c.inverse, c.dt_t, whole) for c in chunks]
        seconds = [_mm3(x, c.inverse, whole) for x, c in zip(firsts, chunks)]
        for c, second in zip(chunks, seconds):
            at, p, tokens = c.at, c.p, c.tokens
            da_t = jnp.where(before, -second, 0.0)
            inv_t = _transposed(c.inverse.astype(dtype), eye_w)
            dv_in = _nn(inv_t, c.du)                    # (W, dv)
            dk_in = _nn(inv_t, c.dw)                    # (W, dk)
            dvs = (c.beta_col * dv_in).astype(dtype)
            for h in range(pack):
                head = p * pack + h
                dv_ref[tokens, head * dv:(head + 1) * dv] = \
                    dvs[h * chunk:(h + 1) * chunk]
            # A = [j < i] beta_i D_ij kk_ij; attn = D o qk
            decay_t = decays_t(c)
            kk, qk_t = _nt(c.k, c.k), _nt(c.k, c.q)
            pair = da_t * decay_t * kk
            of_attn = _nt(c.u, c.do) * decay_t          # (W, W)
            dkk_x = (da_t * c.beta_row * decay_t).astype(dtype)
            dqk_x = of_attn.astype(dtype)
            dbeta_ref[at, p:p + 1, :] = jnp.sum(pair, axis=0, keepdims=True)
            # G^T = (dD o D)^T: token m's g gets its sum over l < m <= i
            within = _summed(pair * c.beta_row + of_attn * qk_t, from_on)
            form_ref[at, p:p + 1, :] = jnp.sum(
                jnp.where(before, within, 0.0), axis=0, keepdims=True)
            # the (tokens, d) operands: q_in = e q, k_in = beta e k, v_in =
            # beta v, k_out = tail k
            tail_col = jnp.exp(c.last_col - c.cs_col)
            of_k_in, of_k_out = dk_in * c.kf, c.dk_out * c.kf
            c.dq = c.e_col * c.dq_in + _nn(_transposed(dqk_x, eye_w), c.k)
            c.dkey = (c.beta_col * c.e_col) * dk_in + tail_col * c.dk_out \
                + _nn(dqk_x, c.q) + _nn(dkk_x, c.k) \
                + _nn(_transposed(dkk_x, eye_w), c.k)
            to_beta = _lane_sums(dv_in * c.vf, c.e_col * of_k_in)
            pulled = tail_col * of_k_out
            to_cs = jnp.sum(
                c.e_col * (c.beta_col * of_k_in
                           + c.dq_in * c.q.astype(f32)) - pulled,
                axis=1, keepdims=True)
            # a head's last token's cs also has every tail's and the
            # state's whole decay
            held = total_ref[at, p:p + 1] * c.dstate * c.state
            for h in range(pack):
                rows = slice(h * chunk, (h + 1) * chunk)
                at_last = _all(pulled[rows]) \
                    + _all(held[:, h * dv:(h + 1) * dv])
                to_cs = to_cs + jnp.where(
                    col_sub[:, :1] == (h + 1) * chunk - 1, at_last, 0.0)
            c.to_cs, c.to_beta = to_cs, to_beta
        # the packs of a chunk: dq and dk summed over the heads, and the
        # columns' way back, d cs of pack p on lane p, d beta on R + p
        for n in range(by):
            mine = chunks[n * packs:(n + 1) * packs]
            at, tokens = mine[0].at, mine[0].tokens
            for key in range(keys):
                its = mine[key * a_key:(key + 1) * a_key]
                at_ = slice(key * dk, (key + 1) * dk)
                dq_ref[tokens, at_] = sum(
                    c.dq[h * chunk:(h + 1) * chunk]
                    for c in its for h in range(pack)).astype(dq_ref.dtype)
                dk_ref[tokens, at_] = sum(
                    c.dkey[h * chunk:(h + 1) * chunk]
                    for c in its for h in range(pack)).astype(dk_ref.dtype)
            dcols = jnp.zeros((w, LANES), f32)
            for c in mine:
                dcols = jnp.where(col_lane == c.p, c.to_cs, dcols)
                dcols = jnp.where(col_lane == n_rows + c.p, c.to_beta, dcols)
            drows = _as_rows(dcols)
            dg_ref[at] = drows[:n_rows]
            dbeta_ref[at, :packs] += drows[n_rows:n_rows + packs]
            if packs < n_rows:
                dbeta_ref[at, packs:] = jnp.zeros((n_rows - packs, w), f32)
        return carry

    jax.lax.fori_loop(0, iterations, rest, None)
    # cs is the running sum of the rows the kernel was given
    _sums_of_a_step(dg_ref, dg_ref, chunk, reverse=True)
    dg_ref[...] += form_ref[...]


# ---------------------------------------------------------------------------
# The calls
# ---------------------------------------------------------------------------

def _specs(q, v, g, r: int, chunk: int, keys: int, reverse: bool):
    """(grid, then the blocks of q or k, v, a plane of rows and a step's
    state and its inverses, then the scratch both kernels have first: the
    (dk, keys r dv) states,
    a step's (k, R, W) ``cs`` and ``cs_last`` and (k, R, P dv) whole decays,
    and a pack's ``u_own``). ``reverse``: the steps from a sample's last
    chunks to its first."""
    b, groups, c, n_rows, w = g.shape            # groups of ``keys`` keys
    key_lanes, lanes = q.shape[2] // groups, v.shape[2] // groups
    dk, dv = key_lanes // keys, lanes // (keys * r)
    k = chunks_a_step(c, chunk, r, dk, dv, v.dtype.itemsize, keys)
    steps = c // k
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    return ((b, groups, steps),
            pl.BlockSpec((None, k * chunk, key_lanes),
                         lambda s, h, i: (s, at(i), h)),
            pl.BlockSpec((None, k * chunk, lanes),
                         lambda s, h, i: (s, at(i), h)),
            pl.BlockSpec((None, None, k, n_rows, w),
                         lambda s, h, i: (s, h, at(i), 0, 0)),
            pl.BlockSpec((None, None, None, dk, lanes),
                         lambda s, h, i: (s, h, at(i), 0, 0)),
            pl.BlockSpec((None, None, k, keys * r * chunk // w, chunk, w),
                         lambda s, h, i: (s, h, at(i), 0, 0, 0)),
            [pltpu.VMEM((dk, lanes), jnp.float32),
             pltpu.VMEM((k, n_rows, w), jnp.float32),
             pltpu.VMEM((k, n_rows, w), jnp.float32),
             pltpu.VMEM((k, n_rows, w // chunk * dv), jnp.float32),
             pltpu.VMEM((k, keys * r * chunk // w, w, dv), jnp.float32)])


def _kept_shapes(q, v, g, r: int, chunk: int, keys: int, steps: int):
    """What the forward keeps for the backward: the state each grid step
    starts from, and every chunk's inverses."""
    b, groups, c, _, w = g.shape
    f32 = jnp.float32
    return [jax.ShapeDtypeStruct(
        (b, groups, steps, q.shape[2] // (groups * keys),
         v.shape[2] // groups), f32),
        jax.ShapeDtypeStruct((b, groups, c, keys * r * chunk // w, chunk, w),
                             f32)]


@functools.partial(jax.jit,
                   static_argnames=("r", "chunk", "keys", "keep",
                                    "interpret"))
def _fwd_call(q, k, v, g, beta, *, r, chunk, keys, keep, interpret):
    """``o``, and where ``keep`` the state each grid step starts from and
    every chunk's inverses."""
    grid, narrow, wide, small, state, kept, scratch = _specs(
        q, v, g, r, chunk, keys, False)
    chunks, _, w = small.block_shape[2:]
    dk = state.block_shape[3]
    packs = scratch[-1].shape[1]
    of = lambda *shape: pltpu.VMEM((chunks, packs) + shape, v.dtype)
    with jax.named_scope(SCOPE):
        out = pl.pallas_call(
            functools.partial(_delta_rule_fwd_kernel, r=r, chunk=chunk,
                              keys=keys, keep=keep),
            grid=grid,
            in_specs=[narrow, narrow, wide, small, small],
            out_specs=[wide] + [state, kept] * keep,
            out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)]
            + _kept_shapes(q, v, g, r, chunk, keys, grid[2]) * keep,
            scratch_shapes=scratch + [of(2 * w, dk), of(w, w), of(dk, w)],
            compiler_params=_PARAMS,
            interpret=interpret,
        )(q, k, v, g, beta)
    return tuple(out) if keep else out[0]


@functools.partial(jax.jit,
                   static_argnames=("r", "chunk", "keys", "interpret"))
def _bwd_call(q, k, v, g, beta, starts, inverses, do, *, r, chunk, keys,
              interpret):
    """(dq, dk, dv, dg, dbeta)."""
    grid, narrow, wide, small, state, kept, scratch = _specs(
        q, v, g, r, chunk, keys, True)
    chunks, n_rows, w = small.block_shape[2:]
    dk, lanes = state.block_shape[3:]
    packs, dv = scratch[-1].shape[1], lanes // (keys * r)
    f32 = jnp.float32
    of = lambda *shape: pltpu.VMEM((chunks, packs) + shape, v.dtype)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    with jax.named_scope(SCOPE):
        return tuple(pl.pallas_call(
            functools.partial(_delta_rule_bwd_kernel, r=r, chunk=chunk,
                              keys=keys),
            grid=grid,
            in_specs=[narrow, narrow, wide, small, small, state, kept,
                      wide],
            out_specs=[narrow, narrow, wide, small, small],
            out_shape=[like(q), like(k), like(v), like(g), like(beta)],
            scratch_shapes=scratch + [
                of(w, dk), of(dk, w),                  # w, k_out^T
                pltpu.VMEM((dk, lanes), f32),          # dS
                pltpu.VMEM((chunks, n_rows, w), f32),  # the tables' d g
                pltpu.VMEM((chunks, dk, lanes), f32),  # S a chunk
                pltpu.VMEM((chunks, dk, lanes), f32),  # dS a chunk
                of(w, dv), of(w, dv),                  # U, dU
                of(w, w), of(w, dk),                   # attn^T, k_out
                of(dk, w), of(dk, w)],                 # q_in^T, w^T
            compiler_params=_PARAMS,
            interpret=interpret,
        )(q, k, v, g, beta, starts, inverses, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _core(q, k, v, g, beta, r: int, chunk: int, keys: int, interpret: bool):
    return _fwd_call(q, k, v, g, beta, r=r, chunk=chunk, keys=keys,
                     keep=False, interpret=interpret)


def _core_fwd(q, k, v, g, beta, r, chunk, keys, interpret):
    o, *kept = _fwd_call(q, k, v, g, beta, r=r, chunk=chunk, keys=keys,
                         keep=True, interpret=interpret)
    # named on the residuals themselves (and on ``o``, which the layer's
    # norm reads again), so that a remat policy can keep them and the
    # backward pass runs neither the forward kernel nor the caller's code
    # for ``q``, ``k`` and the rows again (``v`` is the taps' to keep)
    o, *kept = (checkpoint_name(x, KEPT_MADE) for x in (o, *kept))
    q, k, g, beta = (checkpoint_name(x, KEPT_READ) for x in (q, k, g, beta))
    return o, (q, k, v, g, beta, *kept)


def _core_bwd(r, chunk, keys, interpret, res, do):
    return _bwd_call(*res, do, r=r, chunk=chunk, keys=keys,
                     interpret=interpret)


_core.defvjp(_core_fwd, _core_bwd)


def rule(q, k, v, g, beta, *, key_heads: int, chunk: int,
         interpret: bool = False) -> jax.Array:
    """``chunked_delta_rule(q, k, v, g, beta)`` with the kernels, where
    :func:`fits`. q, k: (B, T, G dk); v: (B, T, H dv); g, beta: (B, T, H)
    f32. The rows the kernels read (a pack of heads a sublane, their
    tokens of a chunk one head after another on the lanes) are XLA code
    here, and their gradient is JAX's differentiation of it. Gradient
    residuals: the operands, the state each grid step starts from and every
    chunk's inverses; all but ``v``, and ``o``, carry the names a layer's
    rematerialisation keeps them by (``KEPT``)."""
    b, t, _ = v.shape
    r = g.shape[-1] // key_heads
    pack = pack_of(r, chunk)
    keys = keys_a_step(key_heads, r, chunk)
    groups, packs = key_heads // keys, keys * r // pack
    pad = ((0, 0),) * 3 + ((0, _rows(packs) - packs), (0, 0))
    by_row = lambda x: jnp.pad(
        x.astype(jnp.float32)
        .reshape(b, t // chunk, chunk, groups, packs, pack)
        .transpose(0, 3, 1, 4, 5, 2)
        .reshape(b, groups, t // chunk, packs, pack * chunk), pad)
    return _core(q, k, v, by_row(g), by_row(beta), r, chunk, keys,
                 interpret)
