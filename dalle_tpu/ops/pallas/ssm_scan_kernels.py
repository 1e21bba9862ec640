"""The Mamba-2 chunked scan as a forward and a backward Mosaic kernel: a
chunk's masked (chunk x chunk) form and the carried (P x N) states stay in
VMEM.

``y_t = S_t C_t + d x_t`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``
in chunks of Q tokens, the algorithm of ``models/sparse_lm.chunked_scan``
(its module docstring, "How the recurrence runs"): inside a chunk the
masked form a head, ``M[i, j] = [j <= i] exp(cs_i - cs_j) dt_j (C_i .
B_j)`` with ``cs`` the running sum of ``dt a`` inside the chunk; across
chunks the state, ``S_c = exp(cs_last) S_{c-1} + (x to_end)^T B`` with
``to_end_j = exp(cs_last - cs_j) dt_j``, and ``y_i += exp(cs_i) C_i .
S_{c-1}``. As XLA code every (Q x Q) form is an array in HBM, written and
read several times a direction (``f32[64,8,8,128,128]``, 256 MiB a layer
and sequence at ``twotower30b``'s sizes) and the backward is the plain
differentiation of that: 2.0 ms a forward call and 5.4 ms a backward call
where the bytes no implementation avoids take 0.21 ms (PERF.md section 5,
PR 57); the kernels read 0.68 and 1.52 (section 5, PR 58).

A grid step is (sample, group, a few chunks), the chunks last and in turn.
It loads the chunks' ``x`` (tokens, r P) for the group's r = H / G heads,
``B`` and ``C`` (tokens, N), and the group's ``dt a`` and ``dt`` as rows (a
head a sublane, a chunk's tokens on the lanes: the two small f32 arrays are
XLA code around the kernel, :func:`scan`). ``cs`` is a running sum along
the lanes, the whole grid step's at once; its and ``dt``'s columns (a token
a sublane) are a transpose a chunk. ``C B^T`` is one product a group and
chunk, a head's form is made from it in f32, cast and multiplied on the
spot, and the r heads' states are one f32 scratch, kept transposed (N, r P)
so that what is linear in the state is one plain product for the whole
group (``C S``, ``B^T (x to_end)`` with ``B^T`` made once a chunk). Heads
narrower than a lane tile (P = 64: two a tile) are multiplied a tile at a
time and the head's lanes selected after: the MXU is 128 wide either way
and no lane is shifted.

The forward writes ``y`` and, where a gradient will ask (:func:`_core`'s
``custom_vjp`` rule, which under a layer's rematerialisation is the
replay), the state each chunk starts from, (B, G, C, N, r P) f32. The
backward walks the chunks in reverse with the state's cotangent in scratch,
makes each head's form again, and writes ``dx``, ``dB`` and ``dC`` (summed
over the group's heads in VMEM), the cotangents of the ``dt a`` and ``dt``
rows, and a grid step's partial sum of ``d``'s. A token's decay ``dt_m a``
scales every pair (i, l) of a chunk with l < m <= i, so the form's share of
its cotangent is the sum of ``G = dM * M`` over that block: ``G`` times a
strictly triangular matrix of ones, then a masked sum down the columns,
which lands on the rows' layout with no transpose; the state's shares
(through ``exp(cs_i)``, ``to_end`` and the chunk's whole decay) are sums
over a head's lanes, taken for all heads at once as products with a (lanes,
heads) map of ones, and one reverse running sum a grid step. What follows
from the rows' cotangents (``d a``, ``d dt``, the sum to ``d d``) is the
differentiation of :func:`scan`'s XLA code by JAX.

Numerics are ``chunked_scan``'s: ``dt``, the decays, their sums, the form's
exponentials and the states in f32; the products' operands in ``x.dtype``
with f32 accumulation, the form cast to ``x.dtype`` before its product and
the state before ``C S``; cotangents enter the MXU in ``x.dtype`` as a
default-precision product of an f32 cotangent does. On the v5e at the
cell's shape every result lies as far from the same expression in f32 as
the XLA code's does (``scripts/ssm_scan_probe.py``; PERF.md section 6,
PR 58).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# the scope the calls open again inside their jit, so that a trace reads
# ``scan[mosaic]`` under the caller's ``ssm/scan`` (head_norm_kernels.SCOPE)
SCOPE = "scan"
# tokens a grid step: what bounds its blocks in VMEM (the states are 256 KiB
# a chunk at r P = 512, N = 128, twice for the pipeline). On the v5e at the
# cell's size 2 048 read 0.66 ms forward and 1.92 with the replay and the
# backward, 512 0.71 and 2.06, 128 0.96 and 3.87 (PERF.md section 6, PR 58)
STEP_TOKENS = 2048
_VMEM = 64 * 1024 * 1024
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM)


def _rows(heads_a_group: int) -> int:
    """Sublanes a group's rows of ``dt`` take: whole tiles."""
    return -(-heads_a_group // SUBLANES) * SUBLANES


def vmem_bytes(chunk: int, heads_a_group: int, width: int, state: int,
               itemsize: int, chunks: int) -> int:
    """What the backward's blocks (the larger kernel's) hold in VMEM at
    ``chunks`` chunks a grid step, the pipeline's two buffers an operand,
    and its scratch."""
    tokens, lanes = chunks * chunk, heads_a_group * width
    blocks = (3 * tokens * lanes * itemsize            # x, dy, dx
              + 4 * tokens * state * itemsize          # B, C, dB, dC
              + 4 * chunks * _rows(heads_a_group) * chunk * 4   # the rows
              + chunks * lanes * state * 4)            # the states
    temporaries = 12 * chunk * max(chunk, LANES) * 4 + 8 * chunk * lanes * 4
    return 2 * blocks + lanes * state * 4 + temporaries


def chunks_a_step(chunks: int, chunk: int, heads_a_group: int, width: int,
                  state: int, itemsize: int) -> int:
    """Chunks a grid step: the most that divide a sample's ``chunks``, keep
    a step within ``STEP_TOKENS`` tokens and its blocks within VMEM."""
    return max(k for k in range(1, max(1, STEP_TOKENS // chunk) + 1)
               if chunks % k == 0 and (k == 1 or vmem_bytes(
                   chunk, heads_a_group, width, state, itemsize, k) <= _VMEM))


def fits(tokens: int, heads: int, width: int, groups: int, state: int,
         chunk: int, itemsize: int) -> Optional[str]:
    """None where the kernels take samples of ``tokens`` tokens of
    ``heads`` heads of ``width`` lanes in ``groups`` groups of a state of
    ``state``, in chunks of ``chunk``; else why not."""
    if tokens % chunk:
        return f"{tokens} tokens are not whole chunks of {chunk}"
    if chunk % LANES:
        return f"a chunk of {chunk} is not whole {LANES}-lane tiles"
    if state % LANES:
        return f"a state of {state} is not whole {LANES}-lane tiles"
    if heads % groups:
        return f"{heads} heads are not whole groups of {groups}"
    r = heads // groups
    if width % LANES and LANES % width:
        return (f"heads of {width} lanes are neither whole {LANES}-lane "
                "tiles nor whole heads a tile")
    if (r * width) % LANES:
        return (f"a group's {r} heads of {width} are not whole "
                f"{LANES}-lane tiles")
    if 2 * _rows(r) > LANES:
        return f"{r} heads a group pass {LANES // 2} rows of a tile"
    need = vmem_bytes(chunk, r, width, state, itemsize, 1)
    if need > _VMEM:
        return (f"a chunk of {chunk} x {r * width} and a state of "
                f"{r * width} x {state} need {need} bytes of VMEM")
    return None


# ---------------------------------------------------------------------------
# What both kernels make of a chunk
# ---------------------------------------------------------------------------

def _running_sum(rows, reverse: bool = False):
    """The running sum along the lanes of (R, Q) f32 rows, a chunk's tokens
    (from the last token back where ``reverse``): log2 Q lane rotates, each
    added where it wrapped around nothing."""
    q = rows.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    by = 1
    while by < q:
        moved = pltpu.roll(rows, q - by if reverse else by, 1)
        rows = rows + jnp.where((lane < q - by) if reverse else (lane >= by),
                                moved, 0.0)
        by *= 2
    return rows


def _sums_of_a_step(rows_ref, cs_ref, reverse: bool = False):
    """The running sums of a grid step's (k, R, Q) rows at once, into
    ``cs_ref``: a chunk's own would be a chain of log2 Q rotates at the
    head of every chunk, with nothing to run beside it."""
    k, n_rows, q = cs_ref.shape
    cs_ref[...] = _running_sum(
        rows_ref[...].reshape(k * n_rows, q), reverse).reshape(k, n_rows, q)


def _columns(rows):
    """(R, Q) rows (a head a sublane, tokens on the lanes) as (Q, 128)
    columns (tokens on the sublanes, head j on lane j)."""
    r, q = rows.shape
    if r < LANES:
        rows = jnp.concatenate(
            [rows, jnp.zeros((LANES - r, q), rows.dtype)], axis=0)
    return rows.T


def _over_lanes(cols, first: int, width: int, span: int):
    """(Q, span): the lanes of head ``first + q`` of the span (``width``
    lanes each) hold column ``first + q`` of ``cols`` (Q, 128)."""
    q = cols.shape[0]
    out = jnp.broadcast_to(cols[:, first:first + 1], (q, span))
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, span), 1)
    for at in range(1, span // width):
        out = jnp.where(lane >= at * width, jnp.broadcast_to(
            cols[:, first + at:first + at + 1], (q, span)), out)
    return out


def _head_lanes(shape, at: int, width: int):
    """Mask of the lanes of the ``at``-th head of a span."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= at * width) & (lane < (at + 1) * width)


def _nt(a, b, precision=None):
    """``a @ b.T``, f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _nn(a, b, precision=None):
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


# Sums and transposes are the MXU's here, as products with noughts and
# ones: a (Q, Q) array's lane reduction or transpose by the XLU takes as
# long as five of its products (PERF.md section 6, PR 58). Such a product
# is exact where its other operand is in ``x.dtype`` (a transpose of what
# the next product reads anyway); an f32 operand goes in as two bfloat16
# pieces, 16 bits of it (with one the step sizes' and the decays' gradients
# lay three times as far from the f32 numbers as the XLA code's, with two
# as far), or whole at the highest precision where ``x.dtype`` is f32.

_HIGHEST = jax.lax.Precision.HIGHEST


def _ones_where(cond, dtype):
    return jnp.where(cond, 1.0, 0.0).astype(dtype)


def _eye(n: int, dtype):
    i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    return _ones_where(i == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1),
                       dtype)


def _transposed(a, eye):
    """``a.T`` of (M, n) ``a`` in ``x.dtype``; ``eye``: (n, n)."""
    return _nt(eye, a, _HIGHEST if a.dtype == jnp.float32 else None
               ).astype(a.dtype)


def _summed(v, ones):
    """``v @ ones`` of f32 ``v`` and noughts and ones in ``x.dtype``."""
    if ones.dtype == jnp.float32:
        return _nn(v, ones, _HIGHEST)
    first = v.astype(ones.dtype)
    rest = (v - first.astype(jnp.float32)).astype(ones.dtype)
    return _nn(first, ones) + _nn(rest, ones)


def _heads_of(span: int, width: int, first: int, dtype):
    """(span, 128) noughts and ones: lane p of a span belongs to the head on
    lane ``first + p // width`` of the (Q, 128) columns."""
    p = jax.lax.broadcasted_iota(jnp.int32, (span, LANES), 0)
    head = jax.lax.broadcasted_iota(jnp.int32, (span, LANES), 1) - first
    return _ones_where((p >= head * width) & (p < (head + 1) * width), dtype)


def _of_a_chunk(cs_rows, dt_rows):
    """What both kernels make of a chunk's rows, each (Q, 128) with head j
    on lane j: the columns of ``cs`` and of ``exp(cs)``, ``exp(cs_last -
    cs)``, and ``to_end``, that times ``dt``."""
    cs_cols = _columns(cs_rows)
    tail_cols = jnp.exp(cs_cols[-1:] - cs_cols)
    return cs_cols, jnp.exp(cs_cols), tail_cols, tail_cols * _columns(dt_rows)


def _decays(cs_row, cs_col):
    """``[j <= i] exp(cs_i - cs_j)`` (Q, Q), i on the sublanes."""
    seg = cs_col - cs_row
    i = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 1)
    return jnp.exp(jnp.where(j <= i, seg, -jnp.inf))


def _ssm_scan_fwd_kernel(x_ref, b_ref, c_ref, decay_ref, dt_ref, d_ref,
                         y_ref, *rest, r, width, chunk, keep):
    """A grid step's chunks in turn. x, y: (k Q, r P); b, c: (k Q, N);
    decay, dt: (k, R, Q) rows, ``dt a`` and ``dt``; d: (1, r P), a head's
    ``d`` on its lanes; where ``keep``, states: (k, N, r P); scratch: the
    state, transposed (N, r P) (the r heads' side by side), and the step's
    ``cs``."""
    states_ref = rest[0] if keep else None
    s_ref, cs_ref = rest[-2:]
    span = max(width, LANES)
    in_span = span // width
    dtype = x_ref.dtype
    f32 = jnp.float32
    eye = _eye(b_ref.shape[1], dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    _sums_of_a_step(decay_ref, cs_ref)

    def one(at, carry):
        tokens = pl.ds(pl.multiple_of(at * chunk, chunk), chunk)
        cs_rows, dt_rows = cs_ref[at], dt_ref[at]
        cs_cols, e_cols, _, end_cols = _of_a_chunk(cs_rows, dt_rows)
        bm, cm = b_ref[tokens, :], c_ref[tokens, :]
        cb = _nt(cm, bm)
        b_t = _transposed(bm, eye)                     # (N, Q)
        state = s_ref[...]
        if keep:
            states_ref[at] = state
        from_state = _nn(cm, state.astype(dtype))      # (Q, r P)
        for s in range(r * width // span):
            lanes = slice(s * span, (s + 1) * span)
            first = s * in_span
            x = x_ref[tokens, lanes]
            y = None
            for q in range(in_span):
                j = first + q
                m = _decays(cs_rows[j:j + 1], cs_cols[:, j:j + 1]) \
                    * dt_rows[j:j + 1] * cb
                own = _nn(m.astype(dtype), x)
                y = own if y is None else jnp.where(
                    _head_lanes(own.shape, q, width), own, y)
            xf = x.astype(f32)
            e_lanes = _over_lanes(e_cols, first, width, span)
            y = y + from_state[:, lanes] * e_lanes
            y = y + xf * d_ref[:, lanes]
            y_ref[tokens, lanes] = y.astype(dtype)
            weighed = (xf * _over_lanes(end_cols, first, width, span)
                       ).astype(dtype)
            s_ref[:, lanes] = e_lanes[chunk - 1:chunk] * state[:, lanes] \
                + _nn(b_t, weighed)
        return carry

    jax.lax.fori_loop(0, cs_ref.shape[0], one, None)


def _ssm_scan_bwd_kernel(x_ref, b_ref, c_ref, decay_ref, dt_ref, d_ref,
                         states_ref, dy_ref, dx_ref, db_ref, dc_ref,
                         ddecay_ref, ddt_ref, dd_ref, ds_ref, cs_ref,
                         form_ref, *, r, width, chunk):
    """A grid step's chunks in reverse, the steps in reverse (the index
    maps). Operands as the forward's, with ``dy`` like ``y``; dx, db, dc,
    ddecay, ddt like x, b, c, decay, dt; dd: (1, r P), the step's sum over
    its tokens of ``dy x``; scratch: the cotangent of the state a chunk
    leaves behind (transposed as the state is), the step's ``cs``, and what
    the forms give the step's ``decay`` rows."""
    span = max(width, LANES)
    in_span = span // width
    spans = r * width // span
    dtype = x_ref.dtype
    f32 = jnp.float32
    k, n_rows, _ = cs_ref.shape
    eye = _eye(chunk, dtype)
    eye_n = eye if c_ref.shape[1] == chunk else _eye(c_ref.shape[1], dtype)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    before, from_on = _ones_where(i < j, dtype), i >= j
    heads_of = [_heads_of(span, width, s * in_span, dtype)
                for s in range(spans)]
    sublane = jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 0)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dd_ref[...] = jnp.zeros_like(dd_ref)
    form_ref[...] = jnp.zeros_like(form_ref)
    _sums_of_a_step(decay_ref, cs_ref)

    def one(step, carry):
        at = k - 1 - step
        tokens = pl.ds(pl.multiple_of(at * chunk, chunk), chunk)
        cs_rows, dt_rows = cs_ref[at], dt_ref[at]
        cs_cols, e_cols, tail_cols, end_cols = _of_a_chunk(cs_rows, dt_rows)
        bm, cm = b_ref[tokens, :], c_ref[tokens, :]
        cb = _nt(cm, bm)
        c_t = _transposed(cm, eye_n)                   # (N, Q)
        state = states_ref[at]
        state_x = state.astype(dtype)
        dstate = ds_ref[...]
        dstate_x = dstate.astype(dtype)
        from_state = _nn(cm, state_x)                  # (Q, r P)
        dweighed = _nn(bm, dstate_x)                   # (Q, r P)
        dcb = jnp.zeros((chunk, chunk), f32)
        db = jnp.zeros(bm.shape, f32)
        dc = jnp.zeros(cm.shape, f32)
        # a token's terms a head, head j on lane j: through exp(cs_i), dy_i
        # . (the state's part of y_i); through to_end, dweighed_j . x_j;
        # and on one row through the state's decay, dS . S
        of_y = jnp.zeros((chunk, LANES), f32)
        of_end = jnp.zeros((chunk, LANES), f32)
        of_state = jnp.zeros((SUBLANES, LANES), f32)
        for s in range(spans):
            lanes = slice(s * span, (s + 1) * span)
            first = s * in_span
            x, dy = x_ref[tokens, lanes], dy_ref[tokens, lanes]
            xf, dyf = x.astype(f32), dy.astype(f32)
            dx = None
            for q in range(in_span):
                row = first + q
                mine = _head_lanes(x.shape, q, width)
                decay = _decays(cs_rows[row:row + 1],
                                cs_cols[:, row:row + 1])
                timed = decay * dt_rows[row:row + 1]
                paired = decay * cb
                m = (timed * cb).astype(dtype)
                own = _nn(_transposed(m, eye), dy)
                dx = own if q == 0 else jnp.where(mine, own, dx)
                dm = _nt(dy if in_span == 1 else jnp.where(
                    mine, dy, jnp.zeros_like(dy)), x)  # (Q, Q)
                dcb = dcb + dm * timed
                # dL[i, l] exp(cs_i - cs_l) summed over i is dt_l's own
                pair = dm * paired
                ddt_ref[at, row:row + 1, :] = jnp.sum(pair, axis=0,
                                                      keepdims=True)
                # and times dt_l the decay of every token from l + 1 to i
                # gets it: token m's the sum over i >= m of the sums over
                # l < m, the second a product with noughts and ones
                within = _summed(pair * dt_rows[row:row + 1], before)
                form_ref[at, row:row + 1, :] = jnp.sum(
                    jnp.where(from_on, within, 0.0), axis=0, keepdims=True)
            e_lanes = _over_lanes(e_cols, first, width, span)
            end_lanes = _over_lanes(end_cols, first, width, span)
            dw = dweighed[:, lanes]
            dx = dx + dyf * d_ref[:, lanes] + dw * end_lanes
            dx_ref[tokens, lanes] = dx.astype(dtype)
            dd_ref[:, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
            dy_e = dyf * e_lanes
            of_y = of_y + _summed(dy_e * from_state[:, lanes], heads_of[s])
            of_end = of_end + _summed(dw * xf, heads_of[s])
            held = e_lanes[chunk - 1:chunk] * dstate[:, lanes]
            of_state = of_state + _summed(jnp.broadcast_to(jnp.sum(
                held * state[:, lanes], axis=0, keepdims=True),
                (SUBLANES, span)), heads_of[s])
            dy_e = dy_e.astype(dtype)
            weighed = (xf * end_lanes).astype(dtype)
            dc = dc + _nt(dy_e, state_x[:, lanes])
            db = db + _nt(weighed, dstate_x[:, lanes])
            ds_ref[:, lanes] = held + _nn(c_t, dy_e)
        dcb_x = dcb.astype(dtype)
        dc_ref[tokens, :] = (dc + _nn(dcb_x, bm)).astype(dc_ref.dtype)
        db_ref[tokens, :] = (db + _nn(_transposed(dcb_x, eye), cm)
                             ).astype(db_ref.dtype)
        # cs_i of a token: its own exp(cs_i), less its to_end's; the last
        # token's also every to_end's of the chunk and the state's decay
        pulled = of_end * end_cols
        at_last = jnp.sum(pulled, axis=0, keepdims=True) + of_state[:1]
        dcs = of_y - pulled + jnp.where(sublane == chunk - 1, at_last, 0.0)
        drows = (dcs + pltpu.roll(of_end * tail_cols, n_rows, 1)).T
        ddecay_ref[at] = drows[:n_rows]
        ddt_ref[at, :r] += drows[n_rows:n_rows + r]
        if r < n_rows:
            ddt_ref[at, r:] = jnp.zeros((n_rows - r, chunk), f32)
        return carry

    jax.lax.fori_loop(0, k, one, None)
    # cs is the running sum of the rows the kernel was given
    _sums_of_a_step(ddecay_ref, ddecay_ref, reverse=True)
    ddecay_ref[...] += form_ref[...]


# ---------------------------------------------------------------------------
# The calls
# ---------------------------------------------------------------------------

def _specs(x, bm, decay, r: int, reverse: bool):
    """(grid, then the blocks of x, B or C, a plane of rows, d and the
    states, then the scratch: the (N, r P) state or its cotangent and a
    step's (k, R, Q) rows). ``reverse``: the steps from a sample's last
    chunks to its first."""
    b, g, c, n_rows, q = decay.shape
    lanes, n = x.shape[2] // g, bm.shape[2] // g
    k = chunks_a_step(c, q, r, lanes // r, n, x.dtype.itemsize)
    steps = c // k
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    return ((b, g, steps),
            pl.BlockSpec((None, k * q, lanes),
                         lambda s, h, i: (s, at(i), h)),
            pl.BlockSpec((None, k * q, n), lambda s, h, i: (s, at(i), h)),
            pl.BlockSpec((None, None, k, n_rows, q),
                         lambda s, h, i: (s, h, at(i), 0, 0)),
            pl.BlockSpec((1, lanes), lambda s, h, i: (0, h)),
            pl.BlockSpec((None, None, k, n, lanes),
                         lambda s, h, i: (s, h, at(i), 0, 0)),
            [pltpu.VMEM((n, lanes), jnp.float32),
             pltpu.VMEM((k, n_rows, q), jnp.float32)])


@functools.partial(jax.jit, static_argnames=("r", "keep", "interpret"))
def _fwd_call(x, bm, cm, decay, dt, d_lanes, *, r, keep, interpret):
    """``y``, and where ``keep`` the state each chunk starts from."""
    b, g, c, _, q = decay.shape
    grid, wide, narrow, small, d_spec, states, scratch = _specs(
        x, bm, decay, r, False)
    group_lanes = x.shape[2] // g
    kept = jax.ShapeDtypeStruct((b, g, c, bm.shape[2] // g, group_lanes),
                                jnp.float32)
    with jax.named_scope(SCOPE):
        out = pl.pallas_call(
            functools.partial(_ssm_scan_fwd_kernel, r=r,
                              width=group_lanes // r, chunk=q, keep=keep),
            grid=grid,
            in_specs=[wide, narrow, narrow, small, small, d_spec],
            out_specs=[wide] + [states] * keep,
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)]
            + [kept] * keep,
            scratch_shapes=scratch,
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, bm, cm, decay, dt, d_lanes)
    return tuple(out) if keep else out[0]


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def _bwd_call(x, bm, cm, decay, dt, d_lanes, states, dy, *, r, interpret):
    """(dx, dB, dC, ddecay, ddt, dd)."""
    g, q = decay.shape[1], decay.shape[4]
    grid, wide, narrow, small, d_spec, kept, scratch = _specs(
        x, bm, decay, r, True)
    steps = grid[2]
    group_lanes = x.shape[2] // g
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    with jax.named_scope(SCOPE):
        *grads, dd = pl.pallas_call(
            functools.partial(_ssm_scan_bwd_kernel, r=r,
                              width=group_lanes // r, chunk=q),
            grid=grid,
            in_specs=[wide, narrow, narrow, small, small, d_spec, kept,
                      wide],
            out_specs=[wide, narrow, narrow, small, small,
                       pl.BlockSpec((None, None, 1, group_lanes),
                                    lambda s, h, i: (s, steps - 1 - i, 0,
                                                     h))],
            out_shape=[like(x), like(bm), like(cm), like(decay), like(dt),
                       jax.ShapeDtypeStruct(
                           (x.shape[0], steps, 1, x.shape[2]), jnp.float32)],
            # and what the forms give the step's ``decay`` rows
            scratch_shapes=scratch + scratch[1:],
            compiler_params=_PARAMS,
            interpret=interpret,
        )(x, bm, cm, decay, dt, d_lanes, states, dy)
        return (*grads, jnp.sum(dd, axis=(0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _core(x, bm, cm, decay, dt, d_lanes, r: int, interpret: bool):
    return _fwd_call(x, bm, cm, decay, dt, d_lanes, r=r, keep=False,
                     interpret=interpret)


def _core_fwd(x, bm, cm, decay, dt, d_lanes, r, interpret):
    y, states = _fwd_call(x, bm, cm, decay, dt, d_lanes, r=r, keep=True,
                          interpret=interpret)
    return y, (x, bm, cm, decay, dt, d_lanes, states)


def _core_bwd(r, interpret, res, dy):
    return _bwd_call(*res, dy, r=r, interpret=interpret)


_core.defvjp(_core_fwd, _core_bwd)


def scan(x, bm, cm, dt, a, d, *, heads: int, groups: int, chunk: int,
         interpret: bool = False) -> jax.Array:
    """``chunked_scan(x, bm, cm, dt, a, d)`` with the kernels, where
    :func:`fits`. x: (B, T, H P); bm, cm: (B, T, G N); dt: (B, T, H) f32;
    a, d: (H,) f32. The rows the kernels read (``dt a`` and ``dt``, a head
    a sublane and a chunk's tokens on the lanes) are XLA code here, and
    their gradient is JAX's differentiation of it. Gradient residuals: the
    operands and the state each chunk starts from."""
    b, t, lanes = x.shape
    r = heads // groups
    f32 = jnp.float32
    by_row = dt.astype(f32).reshape(b, t // chunk, chunk, groups, r) \
        .transpose(0, 3, 1, 4, 2)                      # (b, g, c, r, q)
    pad = ((0, 0),) * 3 + ((0, _rows(r) - r), (0, 0))
    decay = jnp.pad(by_row * a.astype(f32).reshape(groups, 1, r, 1), pad)
    d_lanes = jnp.repeat(d.astype(f32), lanes // heads)[None]
    return _core(x, bm, cm, decay, jnp.pad(by_row, pad), d_lanes, r,
                 interpret)
