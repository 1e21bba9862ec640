"""Sequence/context parallelism over the mesh's ``sp`` axis.

The reference tames its 1280-token sequence with attention *sparsity* (axial
masks + weight sharing, ``task.py:63-66`` of learning-at-home/dalle) and has
no sequence parallelism (SURVEY.md §5). Long-context support is first-class
here: the token axis itself shards over the ``sp`` mesh axis, so sequences
can grow past one chip's HBM. Two schemes, both explicit ``shard_map``
programs whose collectives ride the ICI:

- **Ring attention** (:func:`ring_attention`) — for ``full`` (plain-causal)
  layers. Each device holds one contiguous sequence shard of q/k/v; k/v
  blocks rotate around the ring via ``lax.ppermute`` while a flash-style
  online softmax (running max / normalizer / weighted accumulator)
  accumulates each query block's attention over every key block. Score
  matrices never exceed (shard, shard), so attention memory is O(T²/sp²)
  per device and the full (T, T) matrix never exists anywhere.

- **Ulysses all-to-all** (:func:`ulysses_attention`) — for the whole zoo
  (axial/conv_like masks don't decompose along a contiguous ring).
  ``lax.all_to_all`` re-shards q/k/v from sequence-sharded to head-sharded,
  every device runs the unmodified zoo kernel on the full sequence for its
  subset of heads, and a second all-to-all restores sequence sharding.
  Requires ``heads / tp`` divisible by ``sp``.

:func:`sp_zoo_attention` dispatches: ring for ``full`` layers when
``mode="ring"``, Ulysses otherwise. Composes with the ``dp``/``fsdp`` batch
axes and ``tp`` head sharding (q/k/v enter as (B, T, H, d) with
``P((dp, fsdp), sp, tp, None)``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dalle_tpu.config import ATTN_FULL, SP_RING, SP_ULYSSES
from dalle_tpu.models.attention import zoo_attention
from dalle_tpu.parallel.mesh import shard_map_unbound, unbound_axes

BATCH_AXES: Tuple[str, ...] = ("dp", "fsdp")


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   index: jax.Array, *, axis_name: str, n_shards: int,
                   vary_axes: Tuple[str, ...] = ()) -> jax.Array:
    """Per-shard ZIGZAG ring attention body (call inside ``shard_map``).

    q/k/v: (B, T/sp, H, d) local sequence shards, contiguous layout in and
    out (shard i holds global positions [i*T/sp, (i+1)*T/sp)). ``index``:
    (1,) int32, this shard's position i, handed in as a slice of
    ``arange(sp)`` (``axis_index`` does not lower where this ``shard_map``
    is nested in another: parallel/mesh.shard_map_unbound). Global
    semantics: plain causal attention over the full sequence — exactly the
    zoo's ``full`` type.

    Internally the sequence is re-dealt into the ZIGZAG layout (round 2's
    contiguous ring paid a fully-masked — wasted — block matmul per future
    block, ~37% of attention FLOPs at sp=4): split the sequence into 2*sp
    chunks; device i works on chunks (i, 2*sp-1-i). Under causal masking
    that pairing balances every device and every ring step runs exactly
    TWO fully-allowed half-block matmuls — no masked work at all:

    - peeled local step: A x A (diag mask), B x A (full), B x B (diag)
      where A = chunk i (early), B = chunk 2*sp-1-i (late);
    - ring step r >= 1 with k/v pair from shard s=(i-r)%sp: B x A_s is
      ALWAYS fully allowed (every late chunk sees every early chunk), and
      exactly one of A x A_s (s < i) / B x B_s (s > i) is — selected by a
      cheap where() on the scalar r <= i, both fully allowed.

    The zigzag re-deal in/out costs two half-chunk ppermutes each way —
    ~2 extra ring-hop-equivalents against halving the attention matmuls.
    """
    idx = index[0]
    b, tl, h, d = q.shape
    n = n_shards
    scale = d ** -0.5
    half = tl // 2
    if tl % 2:
        raise ValueError(f"zigzag ring needs an even local shard, got {tl}")

    # -- entry re-deal: contiguous (C_{2i} || C_{2i+1}) -> (A, B) ---------
    # chunk C_j lives on device j//2 (low half iff j even) and is owned in
    # zigzag by device min(j, 2n-1-j)
    low_perm = [(i, 2 * i if 2 * i < n else 2 * n - 1 - 2 * i)
                for i in range(n)]
    high_perm = [(i, 2 * i + 1 if 2 * i + 1 < n else 2 * n - 2 - 2 * i)
                 for i in range(n)]
    inv_low = [(dst, src) for (src, dst) in low_perm]
    inv_high = [(dst, src) for (src, dst) in high_perm]
    even = (idx % 2) == 0  # device d's A-chunk C_d is a low half iff d even

    def deal(x):
        lo = jax.lax.ppermute(x[:, :half], axis_name, low_perm)
        hi = jax.lax.ppermute(x[:, half:], axis_name, high_perm)
        a = jnp.where(even, lo, hi)
        bch = jnp.where(even, hi, lo)
        return a, bch

    qa, qb = deal(q)
    ka, kb = deal(k)
    va, vb = deal(v)

    def _vary(x):
        # accumulators start device-invariant but the body makes them
        # device-varying; mark up front so carry types are stable
        return jax.lax.pcast(x, vary_axes, to="varying")

    def fresh():
        return (_vary(jnp.full((b, h, half), -jnp.inf, jnp.float32)),
                _vary(jnp.zeros((b, h, half), jnp.float32)),
                _vary(jnp.zeros((b, h, half, d), jnp.float32)))

    def update(stats, qc, kc, vc, mask=None):
        """One flash-accumulation step of q-chunk against k/v-chunk."""
        m, l, acc = stats
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                       preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = jnp.where(mask[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # -- peeled local step (the only masked matmuls: the two diagonals) ---
    diag = jnp.tril(jnp.ones((half, half), bool))
    stats_a = update(fresh(), qa, ka, va, mask=diag)
    stats_b = update(update(fresh(), qb, ka, va), qb, kb, vb, mask=diag)

    # -- ring: rotate the zigzag k/v PAIR; two unmasked matmuls per step --
    ring = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, r):
        ka_c, kb_c, va_c, vb_c, sa, sb = carry
        ka_n = jax.lax.ppermute(ka_c, axis_name, ring)
        kb_n = jax.lax.ppermute(kb_c, axis_name, ring)
        va_n = jax.lax.ppermute(va_c, axis_name, ring)
        vb_n = jax.lax.ppermute(vb_c, axis_name, ring)
        # after r rotations we hold shard s = (i - r) mod n's pair
        sb = update(sb, qb, ka_n, va_n)        # B x A_s: always allowed
        is_past = r <= idx                     # s < i
        qc = jnp.where(is_past, qa, qb)
        kc = jnp.where(is_past, ka_n, kb_n)
        vc = jnp.where(is_past, va_n, vb_n)
        upd = update((jnp.where(is_past, sa[0], sb[0]),
                      jnp.where(is_past, sa[1], sb[1]),
                      jnp.where(is_past, sa[2], sb[2])), qc, kc, vc)
        sa = tuple(jnp.where(is_past, u, s0) for u, s0 in zip(upd, sa))
        sb = tuple(jnp.where(is_past, s0, u) for u, s0 in zip(upd, sb))
        return (ka_n, kb_n, va_n, vb_n, sa, sb), None

    if n > 1:
        (_, _, _, _, stats_a, stats_b), _ = jax.lax.scan(
            body, (ka, kb, va, vb, stats_a, stats_b),
            jnp.arange(1, n))

    def finish(stats):
        m, l, acc = stats
        return (acc / l[..., None]).transpose(0, 2, 1, 3)

    out_a, out_b = finish(stats_a), finish(stats_b)

    # -- exit re-deal: (A, B) -> contiguous local halves ------------------
    lo = jax.lax.ppermute(jnp.where(even, out_a, out_b), axis_name, inv_low)
    hi = jax.lax.ppermute(jnp.where(even, out_b, out_a), axis_name,
                          inv_high)
    return jnp.concatenate([lo, hi], axis=1).astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str, attn_type: str, text_len: int,
                      grid: int, conv_kernel: int) -> jax.Array:
    """Per-shard Ulysses body (call inside ``shard_map``).

    q/k/v: (B, T/sp, Hl, d). all_to_all trades the sequence sharding for
    head sharding, so the unmodified zoo kernel (any mask type) runs on the
    full sequence with Hl/sp heads, then the output is traded back.
    """
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            tiled=True)
    # One stacked all-to-all for q/k/v rather than three: same bytes on the
    # wire in one collective. The optimization barriers are a CPU-backend
    # workaround: XLA decomposes a tiled all-to-all into a tuple op whose
    # chunk operands must share a layout, but its simplifier can leave them
    # with different ones (transpose vs reshape producers) and the verifier
    # rejects the module; the barrier forces a materialized canonical layout.
    # TPU lowering doesn't take that path, so the barrier is skipped there.
    cpu = jax.default_backend() == "cpu"
    qkv = jnp.stack((q, k, v))                       # (3, B, Tl, Hl, d)
    if cpu:
        qkv = jax.lax.optimization_barrier(qkv)
    qkv = a2a(qkv, split_axis=3, concat_axis=2)      # (3, B, T, Hl/sp, d)
    out = zoo_attention(qkv[0], qkv[1], qkv[2], attn_type=attn_type,
                        text_len=text_len, grid=grid,
                        conv_kernel=conv_kernel)
    if cpu:
        out = jax.lax.optimization_barrier(out)
    return a2a(out, split_axis=1, concat_axis=2)


def sp_zoo_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     mesh: Mesh, mode: str, attn_type: str, text_len: int,
                     grid: int, conv_kernel: int = 11,
                     sp_axis: str = "sp", tp_axis: str = "tp") -> jax.Array:
    """Sequence-parallel zoo attention on global (B, T, H, d) arrays.

    ``mode="ring"`` uses ring attention for ``full`` layers (and requires
    every layer be ``full``, enforced by ``ModelConfig.validate``);
    ``mode="ulysses"`` handles every zoo type. With ``sp == 1`` this is the
    plain local kernel.
    """
    sp = mesh.shape[sp_axis]
    if sp == 1:
        return zoo_attention(q, k, v, attn_type=attn_type, text_len=text_len,
                             grid=grid, conv_kernel=conv_kernel, mesh=mesh)
    b, t, h, d = q.shape
    tp = mesh.shape[tp_axis]
    # inside the gradient accumulation's shard_map (manual over dp) the
    # batch here is one dp shard's, and only the other axes are bound below
    _, axes = unbound_axes(mesh)
    dbatch = 1
    for ax in BATCH_AXES:
        if ax in axes:
            dbatch *= mesh.shape[ax]
    if b % dbatch:
        raise ValueError(
            f"batch {b} not divisible by its data shards ({dbatch})")
    if t % sp:
        raise ValueError(f"sequence {t} not divisible by sp={sp}")
    if mode == SP_RING and t % (2 * sp):
        raise ValueError(
            f"zigzag ring needs the sequence ({t}) divisible by 2*sp="
            f"{2 * sp} (each shard splits into an early and a late chunk)")
    if h % tp:
        raise ValueError(f"heads {h} not divisible by tp={tp}")

    spec = P(BATCH_AXES, sp_axis, tp_axis, None)
    if mode == SP_RING:
        if attn_type != ATTN_FULL:
            raise ValueError(
                f"ring sequence parallelism requires 'full' attention "
                f"layers, got {attn_type!r} (use mode='ulysses')")
        body = functools.partial(ring_attention, axis_name=sp_axis,
                                 n_shards=sp, vary_axes=axes)
    elif mode == SP_ULYSSES:
        if (h // tp) % sp:
            raise ValueError(
                f"ulysses needs heads/tp ({h}/{tp}={h // tp}) divisible "
                f"by sp={sp}")
        body = functools.partial(ulysses_attention, axis_name=sp_axis,
                                 attn_type=attn_type, text_len=text_len,
                                 grid=grid, conv_kernel=conv_kernel)
    else:
        raise ValueError(f"unknown sequence-parallel mode {mode!r}")

    operands, specs = (q, k, v), (spec, spec, spec)
    if mode == SP_RING:
        operands += (jnp.arange(sp, dtype=jnp.int32),)
        specs += (P(sp_axis),)
    # the Ulysses body runs the zoo's Pallas kernels on TPU, and
    # pallas_call carries no varying-axes annotation for the checker
    fn = shard_map_unbound(body, mesh, in_specs=specs, out_specs=spec,
                           check_vma=mode == SP_RING)
    return fn(*operands)
