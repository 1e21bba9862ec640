"""Fused Pallas TPU kernels for the DALL-E axial attention zoo.

The XLA lowering of axial attention materializes the (B, L, H, N, S) score
and probability tensors in HBM (f32), which made attention cost ~31% of the
train step at ~1.4% of its FLOPs. These kernels compute
``softmax([q . k_prefix^T ; masked q . k_image^T]) @ [v_prefix; v_image]``
entirely in VMEM, flash-attention style: scores never touch HBM, and the
backward pass recomputes them from q/k plus the saved row statistics
``L = m + log(sum(exp(s - m)))``.

Layout: the kernels read and write the projections' own tokens-major
``(B, T, H*d)`` array, so that no array with a minor dimension of
``head_dim`` exists between ``Dense q/k/v`` and ``Dense out`` (a 64-minor
bf16 array is tiled (8, 128) with half of every tile padding: it occupies
and moves twice its bytes, and wants a transpose to heads-major besides).
One grid step takes the ``(1, T, 128)`` block ``(i, 0, j)``: the whole
sequence of one sample, and the ``128 // head_dim`` heads that share a
128-lane column (two at d = 64, one at 128, four at 32); any other width
is the XLA lowering's (:func:`lane_dense_fits`).

Heads inside a tile are separated by LANE MASKS, not lane slices: head A's
scores contract ``where(lane in A, q, 0)`` with ``k`` over all 128 lanes
(the MXU is 128 deep, so a 64-deep product cost the same), ``e_A @
where(lane in A, v, 0)`` is head A's context in A's lanes and exactly zero
elsewhere, so the heads' results add into the tile, which is stored once,
full-lane; the backward's ``dq`` (masked ``k``), ``dk`` (masked ``q``) and
``dv`` (masked ``do``) follow the same pattern. No value is ever shifted
across lanes. On the chip (v5e, the flagship's shapes, PERF.md §6, PR 28)
the masks cost nothing measurable, and lane slices (``ref[..., 64:128]``,
which Mosaic lowers) ran the forward 55% slower; the heads of a tile are
computed side by side, group by group, which gives the scheduler two
independent chains (a tenth off the backward against one head after the
other). Scores, softmax and statistics are f32 and the MXU operands are in
the operands' dtype (bf16 in training), but for the backward's ``dv = p^T
do``, which multiplies the f32 probabilities by ``do`` widened to f32
(at Mosaic's default precision the v5e's MXU rounds both to bf16 all the
same: bit-equal to bf16 operands on the chip, PERF.md §6, PR 28).

Rows 0:``text_len`` of a tile are the text line (causal, no prefix) and the
prefix of every image row, so text and image rows are one call, and the
text rows' ``dk``/``dv`` (own causal part + prefix part) are summed in
VMEM. Every image row's scores against the prefix are one chunky matmul a
head; beside the prefix an image row (the (grid x grid) raster, flattened)
sees keys of its own group:

- line kernels (``_fwd_kernel`` / ``_bwd_kernel``; axial_row, axial_col):
  ``block_rows`` = 128 query rows = 4 lines of 32 — packing lines into the
  MXU's 128-row tiles; cross-line score positions are masked
  (block-diagonal causal mask), trading 3/4 of the tiny line-score FLOPs
  for full systolic utilization. axial_col's lines are raster columns:
  the image rows are reordered column-major around the call in XLA (one
  lane-dense copy an operand), inside the ``custom_vjp`` so that autodiff
  sees no slice.
- window kernels (``_win_fwd_kernel`` / ``_win_bwd_kernel``; conv_like,
  full): each group's keys are the contiguous raster slice that covers
  every query's window (conv_like: the group's raster lines +/- half the
  kernel; full: everything up to the group's end), masked exactly; the
  backward accumulates dk/dv across overlapping groups in VMEM scratch.

Reference capability: the sparse attention classes of dalle-pytorch
(selected at task.py:63-64 of learning-at-home/dalle); SURVEY.md §7 names
this kernel zoo hard part #2.
"""

from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9
LANES = 128


def lane_dense_fits(width: int, head_dim: int, t: int, text_len: int,
                    itemsize: int = 2,
                    budget_bytes: int = 15 * 2 ** 20) -> Optional[str]:
    """Why the kernels should not take a (local) ``(B, t, width)`` operand
    of ``width // head_dim`` heads — None if they should. One predicate
    for the line and the window kernels:

    - whole heads share a 128-lane tile (``128 % head_dim == 0``) and the
      width is whole tiles: an odd number of 64-wide heads (a ``tp`` that
      leaves a shard 3 heads) would want a 64-lane tile again;
    - a grid step fits VMEM. The backward holds eight (t, 128) tiles
      (q/k/v, o/do, dq/dk/dv), double-buffered, for EVERY head of the
      tile the whole-tile prefix scores and their three derivatives in
      f32 (the heads are computed side by side), and the window kernel's
      two f32 accumulators: 14.7 MB at the flagship's 1280 tokens and two
      heads a tile. The budget is set from sandbox compiles for a v5e
      (PERF.md §6, PR 28): every kernel, both directions, compiles at
      14.7 MB (two heads of 64, 32x32 grid) and 13.4 MB (four heads of
      32, 24x24); not every one does at 15.8 MB (one head of 128, 40x40),
      18.3 MB (two of 64, 36x36) or 23 MB (four of 32 at 32x32: there the
      ``full`` kernel's forward already fails). Past that the dense XLA
      lowering — or, for long contexts, ring/Ulysses sequence parallelism
      — is the right one."""
    if LANES % head_dim or width % LANES:
        return (f"{width // head_dim} heads of {head_dim} do not fill "
                f"{LANES}-lane tiles")
    img = t - text_len
    need = (8 * 2 * t * LANES * itemsize
            + (LANES // head_dim) * 4 * img * text_len * 4
            + 2 * img * LANES * 4)
    if need > budget_bytes:
        return (f"a grid step of {t} tokens needs {need / 2 ** 20:.1f} MiB "
                f"of VMEM, over {budget_bytes / 2 ** 20:.0f}")
    return None


def _head_lanes(width: int, head_dim: int) -> List[Optional[jax.Array]]:
    """A (1, width) lane mask for each head of a tile; ``[None]`` where
    the tile is one head and nothing needs masking."""
    if width == head_dim:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [(lane >= h * head_dim) & (lane < (h + 1) * head_dim)
            for h in range(width // head_dim)]


def _only(x, lanes):
    """``x`` with every lane outside one head's zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _dot(a, b, ca: int, cb: int):
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _put(ref, lo: int, hi: int, per_head) -> None:
    """Rows ``lo:hi`` of a tile from its heads' values, each zero outside
    its own lanes: their sum, in one full-lane store."""
    val = per_head[0]
    for x in per_head[1:]:
        val = val + x
    ref[0, lo:hi, :] = val.astype(ref.dtype)


# ---------------------------------------------------------------------------
# Geometry of the image rows: which keys each group of query rows sees
# ---------------------------------------------------------------------------

class _Group(NamedTuple):
    """Query rows ``lo_q:lo_q+rows`` see key rows ``lo_k:lo_k+cols`` (both
    counted from the first image row) where ``mask()`` holds."""
    lo_q: int
    rows: int
    lo_k: int
    cols: int
    mask: Callable[[], jax.Array]


def _line_mask(rows: int, n: int) -> jax.Array:
    """(rows, rows) block-diagonal causal mask: query row i may attend to
    key row j iff they belong to the same length-``n`` line and j <= i."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    return (qi // n == kj // n) & (kj % n <= qi % n)


def _block_rows(t: int, n: int) -> int:
    """Rows per packed group: whole lines only, and the group count must
    divide the line count. Lines shorter than 128 rows are packed up to the
    MXU's 128-row tile; longer lines are processed one whole line per
    group so causality inside the line stays within a single score tile."""
    n_lines = t // n
    lines_per_block = max(1, min(n_lines, 128 // n if n < 128 else 1))
    while n_lines % lines_per_block:
        lines_per_block -= 1
    return n * lines_per_block


def _line_groups(img: int, n: int) -> List[_Group]:
    rows = _block_rows(img, n)
    mask = _line_mask(rows, n)       # one mask serves every group
    return [_Group(lo, rows, lo, rows, lambda: mask)
            for lo in range(0, img, rows)]


def _group_rows(t: int) -> int:
    gs = min(128, t)
    while t % gs:
        gs -= 1
    return gs


def _win_bounds(g: int, gs: int, grid: int, hw, t: int):
    """Static key-slice bounds [lo, hi) for query group ``g``."""
    if hw is None:
        return 0, min(t, (g + 1) * gs)
    first_line = (g * gs) // grid
    last_line = (g * gs + gs - 1) // grid
    n_lines = t // grid
    lo = max(0, first_line - hw) * grid
    hi = (min(n_lines - 1, last_line + hw) + 1) * grid
    return lo, hi


def _win_mask(lo_q: int, rows: int, lo_k: int, cols: int, grid: int, hw):
    qi = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) + lo_q
    ki = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1) + lo_k
    m = ki <= qi
    if hw is not None:
        qr, qc = qi // grid, qi % grid
        kr, kc = ki // grid, ki % grid
        m &= (jnp.abs(kr - qr) <= hw) & (jnp.abs(kc - qc) <= hw)
    return m


def _window_groups(img: int, grid: int, hw) -> List[_Group]:
    gs = _group_rows(img)
    groups = []
    for g in range(img // gs):
        lo_k, hi_k = _win_bounds(g, gs, grid, hw, img)
        groups.append(_Group(
            g * gs, gs, lo_k, hi_k - lo_k,
            functools.partial(_win_mask, g * gs, gs, lo_k, hi_k - lo_k,
                              grid, hw)))
    return groups


# ---------------------------------------------------------------------------
# One head's math on loaded VMEM values (pure jnp), shared by all kernels
# ---------------------------------------------------------------------------

def _prefix_scores(q_img, kt, scale):
    """(rows, S) prefix scores and their row maxima: every image query
    attends to the whole text prefix, so this is one chunky matmul."""
    s_p = _dot(q_img, kt, 1, 1) * scale
    return s_p, jnp.max(s_p, axis=-1, keepdims=True)


def _prefix_grads(q_img, kt, vt, dd, do_img, lse, scale):
    """Whole-tile prefix backward: the image rows' dq through the text
    keys (f32, unscaled) and the text keys' / values' prefix part of
    dk / dv."""
    s_p, _ = _prefix_scores(q_img, kt, scale)
    p_p = jnp.exp(s_p - lse)
    ds_p = p_p * (_dot(do_img, vt, 1, 1) - dd)
    dq_p = _dot(ds_p.astype(kt.dtype), kt, 1, 0)
    dk_p = _dot(ds_p.astype(q_img.dtype), q_img, 0, 0) * scale
    dv_p = _dot(p_p, do_img.astype(jnp.float32), 0, 0)
    return dq_p, dk_p, dv_p


def _group_fwd(qg, kg, vg, mask, scale, prefix=None):
    """One group of query rows: softmax over [prefix ; masked keys] times
    the values. ``prefix`` = (scores (rows, S), their maxima, vt): an
    image group's; the text rows have none. Returns the normalised
    context (rows, lanes) and ``m + log(denom)`` (rows, 1), both f32."""
    s = jnp.where(mask, _dot(qg, kg, 1, 1) * scale, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if prefix is not None:
        s_p, m_p, vt = prefix
        m = jnp.maximum(m, m_p)
    e = jnp.exp(s - m)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    o = _dot(e.astype(vg.dtype), vg, 1, 0)
    if prefix is not None:
        e_p = jnp.exp(s_p - m)
        denom = denom + jnp.sum(e_p, axis=-1, keepdims=True)
        o = o + _dot(e_p.astype(vt.dtype), vt, 1, 0)
    return o / denom, m + jnp.log(denom)


def _group_bwd(qg, kg, vg, dog, dd, lse, mask, scale):
    """One group's (dq, dk, dv) through its own keys, in f32, from the
    recomputed probabilities; an image group's dq still wants its prefix
    part, and every dq the scale."""
    s = jnp.where(mask, _dot(qg, kg, 1, 1) * scale, NEG_INF)
    p = jnp.exp(s - lse)
    ds = p * (_dot(dog, vg, 1, 1) - dd)
    dq = _dot(ds.astype(kg.dtype), kg, 1, 0)
    dk = _dot(ds.astype(qg.dtype), qg, 0, 0) * scale
    dv = _dot(p, dog.astype(jnp.float32), 0, 0)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Kernel bodies: a (1, T, lanes) tile = rows 0:text_len text, then image
# ---------------------------------------------------------------------------

def _forward(q_ref, k_ref, v_ref, out_ref, stats_ref, groups, *,
             scale: float, head_dim: int, text_len: int):
    s = text_len
    heads = _head_lanes(q_ref.shape[2], head_dim)
    # prefix scores of every image row in one chunky matmul a head; only
    # the small masked blocks loop
    prefix = [_prefix_scores(_only(q_ref[0, s:, :], lanes), k_ref[0, :s, :],
                             scale) for lanes in heads]

    def attend(lo_q, hi_q, lo_k, hi_k, mask, rows=None):
        """Rows lo_q:hi_q over keys lo_k:hi_k (and, ``rows`` = their place
        among the image rows, over the prefix): the heads of the tile
        side by side, two independent chains to schedule."""
        outs = []
        for h, lanes in enumerate(heads):
            o, lse = _group_fwd(
                _only(q_ref[0, lo_q:hi_q, :], lanes), k_ref[0, lo_k:hi_k, :],
                _only(v_ref[0, lo_k:hi_k, :], lanes), mask, scale,
                None if rows is None else (
                    prefix[h][0][rows], prefix[h][1][rows],
                    _only(v_ref[0, :s, :], lanes)))
            stats_ref[0, h, 0, lo_q:hi_q] = lse[:, 0]
            outs.append(o)
        _put(out_ref, lo_q, hi_q, outs)

    attend(0, s, 0, s, _line_mask(s, s))        # the text line, causal
    for g in groups:
        attend(s + g.lo_q, s + g.lo_q + g.rows, s + g.lo_k,
               s + g.lo_k + g.cols, g.mask(),
               slice(g.lo_q, g.lo_q + g.rows))


def _backward(q_ref, k_ref, v_ref, stats_ref, o_ref, do_ref, dq_ref, dk_ref,
              dv_ref, groups, dk_acc=None, dv_acc=None, *,
              scale: float, head_dim: int, text_len: int):
    """``dk_acc``/``dv_acc``: f32 (image rows, lanes) scratch for groups
    whose keys overlap (the windows); groups that tile the keys (the
    lines) write theirs straight to the refs."""
    s, t = text_len, q_ref.shape[1]
    heads = _head_lanes(q_ref.shape[2], head_dim)

    def head(ref, lo, hi, h):
        return _only(ref[0, lo:hi, :], heads[h])

    def stats(lo, hi, h):
        """(do . o over head h's lanes, lse) of rows lo:hi, (rows, 1)."""
        dd = jnp.sum(head(do_ref, lo, hi, h).astype(jnp.float32)
                     * o_ref[0, lo:hi, :].astype(jnp.float32),
                     axis=-1, keepdims=True)
        return dd, stats_ref[0, h, 0, lo:hi][:, None]

    def grads(lo_q, hi_q, lo_k, hi_k, mask):
        """The tile's heads side by side: [(dq, dk, dv)] a head."""
        return [_group_bwd(
            head(q_ref, lo_q, hi_q, h), head(k_ref, lo_k, hi_k, h),
            v_ref[0, lo_k:hi_k, :], head(do_ref, lo_q, hi_q, h),
            *stats(lo_q, hi_q, h), mask, scale) for h in range(len(heads))]

    text = grads(0, s, 0, s, _line_mask(s, s))
    # whole-tile prefix grads; only the small masked blocks loop
    prefix = []
    for h in range(len(heads)):
        dd, lse = stats(s, t, h)
        prefix.append(_prefix_grads(
            head(q_ref, s, t, h), head(k_ref, 0, s, h), v_ref[0, :s, :], dd,
            head(do_ref, s, t, h), lse, scale))
    _put(dq_ref, 0, s, [dq * scale for dq, _, _ in text])
    # the text rows' keys serve their own causal line and every image
    # row's prefix: both parts meet here, in f32
    _put(dk_ref, 0, s, [own[1] + pfx[1] for own, pfx in zip(text, prefix)])
    _put(dv_ref, 0, s, [own[2] + pfx[2] for own, pfx in zip(text, prefix)])
    if dk_acc is not None:
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
    for g in groups:
        lo_q, hi_q = s + g.lo_q, s + g.lo_q + g.rows
        lo_k, hi_k = s + g.lo_k, s + g.lo_k + g.cols
        dq, dk, dv = zip(*grads(lo_q, hi_q, lo_k, hi_k, g.mask()))
        rows = slice(g.lo_q, g.lo_q + g.rows)
        _put(dq_ref, lo_q, hi_q, [(own + pfx[0][rows]) * scale
                                  for own, pfx in zip(dq, prefix)])
        if dk_acc is None:
            _put(dk_ref, lo_k, hi_k, dk)
            _put(dv_ref, lo_k, hi_k, dv)
        else:
            keys = slice(g.lo_k, g.lo_k + g.cols)
            dk_acc[keys, :] += sum(dk[1:], dk[0])
            dv_acc[keys, :] += sum(dv[1:], dv[0])
    if dk_acc is not None:
        dk_ref[0, s:, :] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, s:, :] = dv_acc[...].astype(dv_ref.dtype)


# The kernels proper. The benchmark's census and chip_smoke.py look these
# four up BY NAME in the lowered step: keep the names.

def _fwd_kernel(q_ref, k_ref, v_ref, out_ref, stats_ref, *, n: int, **kw):
    img = q_ref.shape[1] - kw["text_len"]
    _forward(q_ref, k_ref, v_ref, out_ref, stats_ref, _line_groups(img, n),
             **kw)


def _bwd_kernel(q_ref, k_ref, v_ref, stats_ref, o_ref, do_ref, dq_ref,
                dk_ref, dv_ref, *, n: int, **kw):
    img = q_ref.shape[1] - kw["text_len"]
    _backward(q_ref, k_ref, v_ref, stats_ref, o_ref, do_ref, dq_ref, dk_ref,
              dv_ref, _line_groups(img, n), **kw)


def _win_fwd_kernel(q_ref, k_ref, v_ref, out_ref, stats_ref, *, grid: int,
                    hw, **kw):
    img = q_ref.shape[1] - kw["text_len"]
    _forward(q_ref, k_ref, v_ref, out_ref, stats_ref,
             _window_groups(img, grid, hw), **kw)


def _win_bwd_kernel(q_ref, k_ref, v_ref, stats_ref, o_ref, do_ref, dq_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, grid: int, hw, **kw):
    img = q_ref.shape[1] - kw["text_len"]
    _backward(q_ref, k_ref, v_ref, stats_ref, o_ref, do_ref, dq_ref, dk_ref,
              dv_ref, _window_groups(img, grid, hw), dk_acc, dv_acc, **kw)


# ---------------------------------------------------------------------------
# pallas_call: one per direction, grid (B, lane tiles)
# ---------------------------------------------------------------------------

def _specs(b: int, t: int, width: int, head_dim: int):
    """Grid and BlockSpecs over ``(B, T, width)``, ``width`` a multiple of
    128: the whole sequence and one 128-lane tile a step; the statistics
    ``(B, H, 1, T)`` go by the tile's heads."""
    tile = pl.BlockSpec((1, t, LANES), lambda i, j: (i, 0, j))
    stats = pl.BlockSpec((1, LANES // head_dim, 1, t),
                         lambda i, j: (i, j, 0, 0))
    return (b, width // LANES), tile, stats


def _call_fwd(kernel, q, k, v, *, head_dim: int, interpret: bool, **kw):
    b, t, width = q.shape
    grid, tile, stats = _specs(b, t, width, head_dim)
    return pl.pallas_call(
        functools.partial(kernel, scale=head_dim ** -0.5, head_dim=head_dim,
                          **kw),
        grid=grid,
        in_specs=[tile, tile, tile],
        out_specs=[tile, stats],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, width // head_dim, 1, t),
                                        jnp.float32)],
        interpret=interpret,
    )(q, k, v)


def _call_bwd(kernel, q, k, v, stats, out, dout, *, head_dim: int,
              interpret: bool, scratch=(), **kw):
    b, t, width = q.shape
    grid, tile, stats_spec = _specs(b, t, width, head_dim)
    return tuple(pl.pallas_call(
        functools.partial(kernel, scale=head_dim ** -0.5, head_dim=head_dim,
                          **kw),
        grid=grid,
        in_specs=[tile, tile, tile, stats_spec, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] * 3,
        scratch_shapes=list(scratch),
        interpret=interpret,
    )(q, k, v, stats, out, dout))


def _named(out, stats):
    """Name the residuals the backward pass needs so a remat save-policy
    (config.remat_policy "save_ctx"/"save_attn") can keep them: without
    this, rematerialisation replays the forward Pallas kernel a second
    time in backward just to regenerate ``stats``/``out``. The names must
    be applied to the residual tracers themselves (naming the custom_vjp
    *output* downstream would leave the pre-name residual unsaved and the
    kernel re-run alive)."""
    return checkpoint_name(out, "attn_out"), checkpoint_name(stats,
                                                             "attn_stats")


# ---------------------------------------------------------------------------
# Line attention: axial_row and axial_col layers
# ---------------------------------------------------------------------------

def _col_major(x, text_len: int, grid: int):
    """The image rows of ``(B, T, width)`` reordered raster <-> column
    major (its own inverse), so that axial_col's lines are contiguous."""
    b, _, width = x.shape
    img = x[:, text_len:].reshape(b, grid, grid, width).swapaxes(1, 2)
    return jnp.concatenate(
        [x[:, :text_len], img.reshape(b, grid * grid, width)], axis=1)


def _line_fwd(q, k, v, head_dim, text_len, grid, by_column, interpret):
    if by_column:
        q, k, v = (_col_major(x, text_len, grid) for x in (q, k, v))
    out, stats = _call_fwd(_fwd_kernel, q, k, v, head_dim=head_dim,
                           text_len=text_len, n=grid, interpret=interpret)
    # the statistics stay in the kernel's row order: only it reads them
    return (_col_major(out, text_len, grid) if by_column else out), stats


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def line_attention(q, k, v, head_dim: int, text_len: int, grid: int,
                   by_column: bool, interpret: bool = False):
    """Fused [text causal ; text prefix || block-diag causal line]
    attention.

    q/k/v: (B, T, H*head_dim), rows 0:text_len text and the rest a
    (grid x grid) raster in raster order; a line is a raster row, or with
    ``by_column`` a raster column (axial_col). Returns (B, T, H*head_dim).
    """
    return _line_fwd(q, k, v, head_dim, text_len, grid, by_column,
                     interpret)[0]


def _line_vjp_fwd(q, k, v, head_dim, text_len, grid, by_column,
                  interpret=False):
    out, stats = _named(*_line_fwd(q, k, v, head_dim, text_len, grid,
                                   by_column, interpret))
    return out, (q, k, v, stats, out)


def _line_vjp_bwd(head_dim, text_len, grid, by_column, interpret, res, dout):
    q, k, v, stats, out = res
    tiles = (q, k, v, out, dout)
    if by_column:
        tiles = tuple(_col_major(x, text_len, grid) for x in tiles)
    q, k, v, out, dout = tiles
    grads = _call_bwd(_bwd_kernel, q, k, v, stats, out, dout,
                      head_dim=head_dim, text_len=text_len, n=grid,
                      interpret=interpret)
    if by_column:
        grads = tuple(_col_major(g, text_len, grid) for g in grads)
    return grads


line_attention.defvjp(_line_vjp_fwd, _line_vjp_bwd)


# ---------------------------------------------------------------------------
# Window attention: conv_like and full layers
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def window_attention(q, k, v, head_dim: int, text_len: int, grid: int, hw,
                     interpret: bool = False):
    """Fused [text causal ; text prefix || raster-window causal] attention.

    q/k/v: (B, T, H*head_dim) as for :func:`line_attention`. ``hw`` = half
    the conv_like kernel (reference conv window, task.py:63); ``hw=None``
    = plain causal ('full'). Returns (B, T, H*head_dim).
    """
    return _call_fwd(_win_fwd_kernel, q, k, v, head_dim=head_dim,
                     text_len=text_len, grid=grid, hw=hw,
                     interpret=interpret)[0]


def _win_vjp_fwd(q, k, v, head_dim, text_len, grid, hw, interpret=False):
    out, stats = _named(*_call_fwd(
        _win_fwd_kernel, q, k, v, head_dim=head_dim, text_len=text_len,
        grid=grid, hw=hw, interpret=interpret))
    return out, (q, k, v, stats, out)


def _win_vjp_bwd(head_dim, text_len, grid, hw, interpret, res, dout):
    q, k, v, stats, out = res
    acc = pltpu.VMEM((q.shape[1] - text_len, LANES), jnp.float32)
    return _call_bwd(_win_bwd_kernel, q, k, v, stats, out, dout,
                     head_dim=head_dim, text_len=text_len, grid=grid, hw=hw,
                     interpret=interpret, scratch=(acc, acc))


window_attention.defvjp(_win_vjp_fwd, _win_vjp_bwd)
