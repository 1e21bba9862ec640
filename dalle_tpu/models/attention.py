"""The DALL-E attention zoo, TPU-first.

The reference model selects per-layer attention types from dalle-pytorch's
zoo — ``full``, ``axial_row``, ``axial_col``, ``conv_like`` (configured at
``task.py:63-64`` of learning-at-home/dalle). Semantics implemented here:

- text tokens attend causally to text tokens only (except ``full``, where the
  whole sequence is plain-causal — equivalent for text positions anyway);
- image token (r, c) attends to ALL text tokens plus, depending on the type:
  * ``full``       — every earlier image token (plain causal),
  * ``axial_row``  — image tokens in the same row with column <= c,
  * ``axial_col``  — image tokens in the same column with row <= r,
  * ``conv_like``  — image tokens inside a k x k window around (r, c) that
                     precede it in raster order (inclusive).

Two implementations are provided:

1. :func:`dense_zoo_attention` — one dense attention with a static (T, T)
   boolean mask from :func:`zoo_attention_mask`. Used for ``full`` and
   ``conv_like`` layers, for autoregressive decoding with a KV cache, and as
   the correctness oracle in tests.
2. :func:`axial_attention` — the batched axial fast path: rows (or columns)
   become a batch axis so the attention score matrix is (C, text+C) instead
   of (T, T); ~4.5x fewer attention FLOPs at the flagship shape.

All matmuls accumulate in float32 (``preferred_element_type``) and softmax
runs in float32, with activations in bfloat16 for the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from dalle_tpu.config import (
    ATTN_AXIAL_COL,
    ATTN_AXIAL_ROW,
    ATTN_CONV_LIKE,
    ATTN_FULL,
    SP_ULYSSES,
)
from dalle_tpu.ops.pallas import lowering
from dalle_tpu.parallel.mesh import LANES_SPEC

NEG_INF = -1e9  # softmax mask fill; safe in fp32 accumulation

# Tests set this True to route the model through the fused Pallas kernels
# in interpret mode on CPU (the dispatchers otherwise pick the kernels
# only on a real TPU backend). Read by ops/pallas/lowering.py alone.
_PALLAS_INTERPRET = False


def _zoo_site(attn_type: str) -> str:
    return f"{attn_type} attention"


def attn_layout_record(cfg, mesh=None) -> str:
    """The attention layers whose traced calls took the lane-dense kernel,
    in words — the ``attn_layout`` attribute of the ``train`` plane's
    ``setup/warmup`` row. Looked up, for this model's own local shapes
    (``mesh``'s ``tp`` splits the heads' lanes; Ulysses its ``sp`` too),
    in what the dispatcher did while tracing, not worked out from the
    shapes again: a layer never traced, one that fell back, or a run with
    no Mosaic backend at all counts as not on the kernel."""
    from dalle_tpu.ops.pallas.attention_kernels import LANES

    shards = 1
    if mesh is not None:
        shards = mesh.shape.get("tp", 1)
        if cfg.sequence_parallel == SP_ULYSSES:
            shards *= mesh.shape.get("sp", 1)
    width = cfg.heads * cfg.head_dim // shards
    sched = cfg.layer_schedule()
    on = sum(lowering.why_not(_zoo_site(t), (
        cfg.head_dim, width, cfg.total_seq_len, cfg.text_seq_len)) is None
        for _, t in sched)
    return f"lane-dense {LANES}: {on} of {len(sched)} layers"


# ---------------------------------------------------------------------------
# Rotary position embeddings (reference: rotary_emb=True, task.py:80)
# ---------------------------------------------------------------------------

def rotary_cos_sin(positions: jax.Array, head_dim: int,
                   base: float = 10000.0,
                   heads: int = 1) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given absolute positions, shape
    (..., heads * head_dim): one head's table, repeated for ``heads`` heads
    side by side (the (B, T, H*d) layout of :func:`apply_rotary_lanes`)."""
    half = head_dim // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=jnp.float32) / half))
    # tiled while still one row: no (T, head_dim) table is ever built
    angles = (positions.astype(jnp.float32)[..., None]
              * jnp.tile(freqs, 2 * heads))
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary_lanes(x: jax.Array, cos: jax.Array, sin: jax.Array,
                       head_dim: int) -> jax.Array:
    """Rotary on the projections' own (..., H*d) array; cos/sin
    broadcastable to it (:func:`rotary_cos_sin` with ``heads``):
    ``x * cos + rotate_half(x) * sin`` in f32 a head, with no array of
    minor dimension ``head_dim`` in between. ``rotate_half`` (a head's
    ``concat(-x2, x1)``) is a shift by half a head along the lanes, up
    for a head's first half and down for its second, which fuses into the
    multiply-adds around it. (As a matmul, ``x @ kron(I, R)`` on a
    (..., H*d/128, 128) view, XLA lays the result out tokens-minor, at
    one transposing copy an operand and direction.)"""
    half = head_dim // 2
    rest = [(0, 0)] * (x.ndim - 1)
    # shifted in x's own dtype, widened after: the projection then hands
    # this fusion its bf16 result, not an f32 copy of it
    up = jnp.pad(x[..., half:], rest + [(0, half)])       # x[lane + half]
    down = jnp.pad(x[..., :-half], rest + [(half, 0)])    # x[lane - half]
    lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[-1],), 0)
    rot = jnp.where(lane % head_dim < half, -up, down).astype(jnp.float32)
    return (x.astype(jnp.float32) * cos + rot * sin).astype(x.dtype)


# ---------------------------------------------------------------------------
# Static masks (oracle + decode path)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def zoo_attention_mask(attn_type: str, text_len: int, grid: int,
                       conv_kernel: int = 11) -> np.ndarray:
    """Boolean (T, T) mask, True = may attend. T = text_len + grid*grid.

    Encodes the per-type sparsity patterns described in the module docstring;
    the dense-mask equivalent of dalle-pytorch's sparse attention classes.
    """
    img_len = grid * grid
    total = text_len + img_len
    idx = np.arange(total)
    causal = idx[None, :] <= idx[:, None]

    mask = np.zeros((total, total), dtype=bool)
    # Text queries: causal over text only (identical to plain causal since
    # nothing precedes the text block).
    mask[:text_len, :text_len] = causal[:text_len, :text_len]

    qi = np.arange(img_len)
    qr, qc = qi // grid, qi % grid
    ki = np.arange(img_len)
    kr, kc = ki // grid, ki % grid

    # Image queries attend to all text.
    mask[text_len:, :text_len] = True

    if attn_type == ATTN_FULL:
        img_img = ki[None, :] <= qi[:, None]
    elif attn_type == ATTN_AXIAL_ROW:
        img_img = (kr[None, :] == qr[:, None]) & (kc[None, :] <= qc[:, None])
    elif attn_type == ATTN_AXIAL_COL:
        img_img = (kc[None, :] == qc[:, None]) & (kr[None, :] <= qr[:, None])
    elif attn_type == ATTN_CONV_LIKE:
        hw = conv_kernel // 2
        window = (np.abs(kr[None, :] - qr[:, None]) <= hw) & \
                 (np.abs(kc[None, :] - qc[:, None]) <= hw)
        img_img = window & (ki[None, :] <= qi[:, None])
    else:
        raise ValueError(f"unknown attention type {attn_type!r}")

    mask[text_len:, text_len:] = img_img
    return mask


# ---------------------------------------------------------------------------
# Dense masked attention
# ---------------------------------------------------------------------------

def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """Masked multi-head attention.

    q: (B, Tq, H, d), k/v: (B, Tk, H, d), mask: broadcastable to (Tq, Tk)
    or (B, 1, Tq, Tk). Returns (B, Tq, H, d) in q.dtype.
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask.ndim == 2:
        mask = mask[None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def dense_zoo_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        attn_type: str, text_len: int, grid: int,
                        conv_kernel: int = 11) -> jax.Array:
    mask = jnp.asarray(zoo_attention_mask(attn_type, text_len, grid,
                                          conv_kernel))
    # named so the save_ctx/save_attn remat policies can keep the dense
    # path's attention output (the Pallas kernels name their own outputs
    # "attn_out"/"attn_stats" instead — each layer emits exactly one set)
    return checkpoint_name(dense_attention(q, k, v, mask), "attn_ctx")


# ---------------------------------------------------------------------------
# Batched axial fast path
# ---------------------------------------------------------------------------

def _text_causal(q_t: jax.Array, k_t: jax.Array, v_t: jax.Array) -> jax.Array:
    """Causal attention over the text prefix. (B, Tt, H, d) -> same."""
    text_len = q_t.shape[1]
    causal = jnp.tril(jnp.ones((text_len, text_len), dtype=bool))
    return dense_attention(q_t, k_t, v_t, causal)


def _axial_lines(q_g: jax.Array, k_g: jax.Array, v_g: jax.Array,
                 k_t: jax.Array, v_t: jax.Array) -> jax.Array:
    """Attention of each grid *line* over [all text || causal same-line].

    q_g/k_g/v_g: (B, L, N, H, d) where L = number of lines (rows or cols)
    and N = tokens per line, causal along N. k_t/v_t: (B, Tt, H, d).
    Returns (B, L, N, H, d).
    """
    scale = q_g.shape[-1] ** -0.5
    n = q_g.shape[2]
    # Scores against text: every image token sees all text tokens.
    s_t = jnp.einsum("blnhd,bshd->blhns", q_g, k_t,
                     preferred_element_type=jnp.float32) * scale
    # Scores within the line, causal.
    s_l = jnp.einsum("blnhd,blmhd->blhnm", q_g, k_g,
                     preferred_element_type=jnp.float32) * scale
    line_causal = jnp.tril(jnp.ones((n, n), dtype=bool))
    s_l = jnp.where(line_causal[None, None, None], s_l, NEG_INF)

    # Joint softmax over [text-scores || line-scores] WITHOUT materializing
    # the concatenation: concat/slice pairs at this size dominated the step
    # profile as HBM copies, while max/exp/sum fuse into the matmuls.
    m = jnp.maximum(jnp.max(s_t, axis=-1), jnp.max(s_l, axis=-1))
    e_t = jnp.exp(s_t - m[..., None])
    e_l = jnp.exp(s_l - m[..., None])
    denom = jnp.sum(e_t, axis=-1) + jnp.sum(e_l, axis=-1)  # (b,l,h,n)
    out = jnp.einsum("blhns,bshd->blnhd", e_t.astype(v_t.dtype), v_t,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("blhnm,blmhd->blnhd", e_l.astype(v_g.dtype), v_g,
                           preferred_element_type=jnp.float32)
    out = out / denom.transpose(0, 1, 3, 2)[..., None]
    return out.astype(q_g.dtype)


def _as_lanes(fn, q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """``fn`` on the (B, T, H*d) view of (B, T, H, d) operands: the
    kernels' own layout (the reshape of a row-major array is a bitcast)."""
    b, t, h, d = q.shape
    return fn(*(x.reshape(b, t, h * d) for x in (q, k, v))).reshape(q.shape)


def _fused_lanes(q: jax.Array, k: jax.Array, v: jax.Array, head_dim: int,
                 attn_type: str, text_len: int, grid: int,
                 conv_kernel: int) -> jax.Array:
    """A zoo layer's Pallas kernel on (B, T, H*d) operands: the line
    kernel for the axial types, the window kernel for conv_like / full."""
    from dalle_tpu.ops.pallas.attention_kernels import (line_attention,
                                                        window_attention)

    interpret = lowering.interpret()
    if attn_type in (ATTN_AXIAL_ROW, ATTN_AXIAL_COL):
        return line_attention(q, k, v, head_dim, text_len, grid,
                              attn_type == ATTN_AXIAL_COL, interpret)
    hw = conv_kernel // 2 if attn_type == ATTN_CONV_LIKE else None
    return window_attention(q, k, v, head_dim, text_len, grid, hw, interpret)


def axial_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    attn_type: str, text_len: int, grid: int) -> jax.Array:
    """Axial row/col attention over [text || image] sequence, the XLA
    lowering.

    q/k/v: (B, T, H, d) with T = text_len + grid*grid. The image block is
    viewed as a (grid, grid) raster; rows (axial_row) or columns (axial_col)
    become a batch dimension so XLA sees large, regular batched matmuls.
    """
    b, t, h, d = q.shape
    q_t, k_t, v_t = (x[:, :text_len] for x in (q, k, v))
    out_t = _text_causal(q_t, k_t, v_t)

    def to_grid(x):
        return x[:, text_len:].reshape(b, grid, grid, h, d)

    q_g, k_g, v_g = to_grid(q), to_grid(k), to_grid(v)
    if attn_type == ATTN_AXIAL_COL:
        # Columns become lines: swap the two grid axes; causal index is then
        # the row index, matching "same column, row <= r".
        q_g, k_g, v_g = (x.swapaxes(1, 2) for x in (q_g, k_g, v_g))

    out_g = _axial_lines(q_g, k_g, v_g, k_t, v_t)

    if attn_type == ATTN_AXIAL_COL:
        out_g = out_g.swapaxes(1, 2)
    out_i = out_g.reshape(b, grid * grid, h, d)
    # named for the save policies (see dense_zoo_attention)
    return checkpoint_name(jnp.concatenate([out_t, out_i], axis=1),
                           "attn_ctx")


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

def zoo_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  attn_type: str, text_len: int, grid: int,
                  conv_kernel: int = 11, mesh=None,
                  scope: Optional[str] = None) -> jax.Array:
    """:func:`zoo_attention_lanes` for (B, T, H, d) operands."""
    return _as_lanes(
        functools.partial(zoo_attention_lanes, head_dim=q.shape[-1],
                          attn_type=attn_type, text_len=text_len, grid=grid,
                          conv_kernel=conv_kernel, mesh=mesh, scope=scope),
        q, k, v)


def zoo_attention_lanes(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        head_dim: int, attn_type: str, text_len: int,
                        grid: int, conv_kernel: int = 11, mesh=None,
                        scope: Optional[str] = None) -> jax.Array:
    """Train-time attention dispatch on the projections' (B, T, H*d)
    arrays: fast paths where available. With a ``mesh`` of more than one
    device the fused kernels run per shard (batch over dp/fsdp, lanes —
    whole heads — over tp; parallel/mesh.per_shard), under the caller's
    ``scope`` so that they keep its name there: the lane-dense kernel
    where ``attention_kernels.lane_dense_fits`` on a shard's local shapes,
    else the XLA lowering."""
    kw = dict(head_dim=head_dim, attn_type=attn_type, text_len=text_len,
              grid=grid, conv_kernel=conv_kernel)
    return lowering.site(
        _zoo_site(attn_type), functools.partial(_lane_dense, **kw),
        functools.partial(_fused_lanes, **kw),
        functools.partial(_xla_on_lanes, **kw),
        mesh, (LANES_SPEC,) * 3, LANES_SPEC, scope)(q, k, v)


def _lane_dense(q: jax.Array, k: jax.Array, v: jax.Array, *, head_dim: int,
                attn_type: str, text_len: int, grid: int,
                conv_kernel: int) -> bool:
    """Whether one shard's (local) shapes take the lane-dense kernel."""
    from dalle_tpu.ops.pallas.attention_kernels import (LANES,
                                                        lane_dense_fits)

    _, t, width = q.shape
    why_not = lane_dense_fits(width, head_dim, t, text_len,
                              q.dtype.itemsize)
    return lowering.chose(
        _zoo_site(attn_type), (head_dim, width, t, text_len), why_not,
        why_not or f"local q{tuple(q.shape)}: {LANES // head_dim} heads to "
        f"a {LANES}-lane tile, text {text_len} + grid {grid} in one call")


def _xla_on_lanes(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  head_dim: int, attn_type: str, text_len: int, grid: int,
                  conv_kernel: int) -> jax.Array:
    """The XLA lowering of a zoo layer, on its (B, T, H, d) view."""
    b, t, width = q.shape
    q, k, v = (x.reshape(b, t, width // head_dim, head_dim)
               for x in (q, k, v))
    if attn_type in (ATTN_AXIAL_ROW, ATTN_AXIAL_COL):
        out = axial_attention(q, k, v, attn_type, text_len, grid)
    else:
        out = dense_zoo_attention(q, k, v, attn_type, text_len, grid,
                                  conv_kernel)
    return out.reshape(b, t, width)
