"""The Mamba-2 mixer's way into and out of its scan as two Mosaic passes a
direction: the taps, bias and SiLU before the scan's kernels, the gate and
the group norm after them.

Both stages are elementwise work on (T, W) arrays with one small coupling
each, along the tokens (K - 1 rows of halo) or along the lanes (a group's
sum), and as XLA code both ran at a sixth of what their bytes allow: four
f32 shifted copies of an (8 192, 6 144) array and three passes over (8 192,
4 096) f32 at ``twotower30b``'s sizes (PERF.md section 5, PR 58). Here a
grid step owns a tile of one sample's tokens by all the stage's lanes, reads
its operands where ``in_proj`` wrote them (column blocks of the (B, T, 2 H P
+ 2 G N + H) array ``[z ; xBC ; dt]``: no slice is traced, so XLA copies
none), works it a block at a time in f32 and writes the scan's operands,
or ``out_proj``'s, once.

**The taps** (:func:`taps_silu`): ``silu(sum_j taps[j] xBC_{t-(K-1)+j} +
bias)``, ``models/sparse_lm.causal_taps_silu``'s arithmetic term for term
(f32 products of the operands widened, the taps' own term and the bias
first, then a term a step back), written as THREE arrays, ``x`` (T, H P),
``B`` and ``C`` (T, G N): the scan kernels' operands. A part of ``xBC`` is a
column block of its own width, so each part's offset in ``in_proj``'s output
has to be a whole number of its widths (:func:`taps_fit`). The rows before a
tile are a second, small block of the same columns (the last sublane tile of
the tile before; noughts before t = 0); the tile is widened once into an f32
scratch behind those rows, and a tap's shifted operand is a sublane rotate of
a block that starts one sublane tile early. The backward kernel reads the
three cotangents and the raw columns, makes the pre-activation again on the
tile (nothing is kept for it), walks the tiles from the last to the first
with the first rows of ``dz`` of the tile after in scratch (the halo the
other way), writes ``d xBC`` as one (T, W) array and sums ``d taps`` and ``d
bias`` in f32 into one block that stays in VMEM along the tokens.

**The gate and the norm** (:func:`gate_norm`): ``rmsnorm over each group's
lanes of (y silu(z)), times scale``, ``sparse_lm.gated_group_norm``'s
arithmetic, ``y`` the scan's output and ``z`` the first column block of
``in_proj``'s. A group's sum over its lanes is its lane tiles added on the
VPU and one product with a (128, 128) block of ones on the MXU, which
leaves the sum on every lane: on the v5e a lane reduction of a tile costs as
much as five such products (PERF.md section 6, PR 58); an f32 addend goes in
as two bfloat16 pieces, or whole at the highest precision where the
operands are f32 (``ssm_scan_kernels._summed``). The backward kernel reads
``y``, ``z``, the cotangent and ``scale``, makes the gate and the inverse
RMS again, and writes ``d y``, ``d z`` and the sum of ``d scale``.

Gradient residuals are the operands alone. The cotangent of ``in_proj``'s
output is each pass's columns padded with noughts to its width, which XLA
fuses into the sum that the projection's backward products read.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_tpu.ops.pallas.geglu_kernels import _pick_block
from dalle_tpu.ops.pallas.ssm_scan_kernels import LANES, SUBLANES, _summed

# the scopes the calls open again inside their jit, so that a trace reads
# ``taps[mosaic]`` under the caller's ``ssm/conv`` and ``gate_norm[mosaic]``
# under ``ssm/gate_norm`` (head_norm_kernels.SCOPE)
TAPS_SCOPE = "taps"
GATE_NORM_SCOPE = "gate_norm"
# tokens a grid step, and the block the kernels work at a time inside it:
# ``CHUNK`` rows of ``SLAB`` lanes of the taps (a few vregs an array: the
# rotates' operands stay in registers), ``NORM_CHUNK`` rows of a group (the
# whole tile: its sums' products are then few and large). On the v5e at the
# cell's size the taps read 0.39 ms forward and 0.70 backward at 32 x 512,
# 0.45 / 0.81 at 16 rows, 0.41 / 0.75 at 64, 0.42 / 0.81 at 1 024 lanes,
# the same at 512 tokens a step; the gate and norm 0.21 / 0.50 at 256 rows,
# 0.28 / 0.52 at 64, 0.67 / 1.13 at 16 (PERF.md section 6, PR 60)
ROWS = 256
CHUNK = 32
NORM_CHUNK = 256
SLAB = 512
_VMEM = 64 * 1024 * 1024


def halo_rows(itemsize: int) -> int:
    """Rows of the block that holds the tokens before a tile: one sublane
    tile of the operands' dtype."""
    return SUBLANES * max(1, 4 // itemsize)


def rows_tile(tokens: int, itemsize: int) -> int:
    """Tokens a grid step: the largest whole number of sublane tiles that
    divides a sample's ``tokens`` within ``ROWS``."""
    return _pick_block(tokens, ROWS, halo_rows(itemsize))


def _chunk(rows: int, target: int, itemsize: int) -> int:
    return _pick_block(rows, target, halo_rows(itemsize))


def _slab(width: int) -> int:
    return SLAB if width % SLAB == 0 else LANES


def _rows_fit(tokens: int, itemsize: int) -> Optional[str]:
    rows = halo_rows(itemsize)
    if tokens % rows:
        return f"{tokens} tokens are not whole tiles of {rows} rows"
    return None


def _parts(before: int, widths: Sequence[int]):
    """((offset in ``in_proj``'s output, offset in ``xBC``, width), ...)."""
    at, parts = 0, []
    for width in widths:
        parts.append((before + at, at, width))
        at += width
    return tuple(parts)


def taps_fit(tokens: int, before: int, widths: Sequence[int], taps: int,
             itemsize: int) -> Optional[str]:
    """None where :func:`taps_silu` takes samples of ``tokens`` tokens whose
    parts of ``widths`` lanes lie side by side from lane ``before`` on, under
    ``taps`` taps; else why not."""
    for at, _, width in _parts(before, widths):
        if width % LANES:
            return f"a part of {width} lanes is not whole {LANES}-lane tiles"
        if at % width:
            return (f"a part of {width} lanes at lane {at} is no column "
                    "block of its width")
    if taps - 1 > SUBLANES:
        return f"{taps} taps reach past the {SUBLANES} rows before a tile"
    why_not = _rows_fit(tokens, itemsize)
    if why_not is None:
        need = _taps_vmem(rows_tile(tokens, itemsize), sum(widths), taps,
                          itemsize)
        if need > _VMEM:
            why_not = (f"a tile of {rows_tile(tokens, itemsize)} x "
                       f"{sum(widths)} needs {need} bytes of VMEM")
    return why_not


def _taps_vmem(rows: int, lanes: int, taps: int, itemsize: int) -> int:
    """What the backward's blocks (the larger kernel's) and scratch hold,
    the pipeline's two buffers an operand."""
    halo = halo_rows(itemsize)
    blocks = (3 * rows + halo) * lanes * itemsize \
        + (2 * taps + 2) * SUBLANES * lanes * 4
    return 2 * blocks + 2 * (rows + SUBLANES) * lanes * 4


def gate_norm_fit(tokens: int, width: int, groups: int,
                  itemsize: int) -> Optional[str]:
    """None where :func:`gate_norm` takes samples of (tokens, width) in
    ``groups`` groups of lanes; else why not."""
    if width % groups:
        return f"{width} lanes are not whole groups of {groups}"
    if (width // groups) % LANES:
        return (f"a group of {width // groups} lanes is not whole "
                f"{LANES}-lane tiles")
    why_not = _rows_fit(tokens, itemsize)
    if why_not is None:
        need = 2 * 5 * rows_tile(tokens, itemsize) * width * itemsize
        if need > _VMEM:
            why_not = (f"a tile of {rows_tile(tokens, itemsize)} x {width} "
                       f"needs {need} bytes of VMEM")
    return why_not


def _sigmoid(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _by_sublane(v):
    """(R, L) summed over its sublane tiles, (8, L): adds of whole vregs;
    the caller sums the 8 rows."""
    return jnp.sum(v.reshape(-1, SUBLANES, v.shape[1]), axis=0)


# ---------------------------------------------------------------------------
# The taps, the bias and the SiLU
# ---------------------------------------------------------------------------

def _stage(buf, at: int, in_ref, halo_ref, first):
    """A part's tile in f32 into ``buf`` at lane ``at``, behind the 8 rows
    before it (noughts where the tile is the sample's ``first``)."""
    width = in_ref.shape[1]
    before = halo_ref[...].astype(jnp.float32)[-SUBLANES:]
    buf[:SUBLANES, at:at + width] = jnp.where(first, 0.0, before)
    buf[SUBLANES:, at:at + width] = in_ref[...].astype(jnp.float32)


def _shifted(ext, taps: int):
    """Of ``ext``, a part's rows r - 8 to r + R, the rows r - back to r + R
    - back for back = 0 .. taps - 1, each (R, L): a sublane rotate of the
    block that starts a sublane tile early."""
    return [ext[SUBLANES:]] + [pltpu.roll(ext, back, 0)[SUBLANES:]
                               for back in range(1, taps)]


def _pre_activation(shifted, taps, bias):
    """``causal_taps_silu``'s sum, in its order: the token's own tap and the
    bias, then a step back a term. taps: (K, L) f32."""
    k = taps.shape[0]
    z = taps[k - 1:k] * shifted[0] + bias
    for back in range(1, k):
        z = z + taps[k - 1 - back:k - back] * shifted[back]
    return z


def _ssm_taps_fwd_kernel(*refs, parts, rc):
    """refs: the parts' tiles, the parts' rows before, taps (K, W), bias
    (1, W), the parts' outputs; scratch: a tile in f32 behind 8 rows."""
    n = len(parts)
    ins, halos, (taps_ref, bias_ref), outs, buf = (
        refs[:n], refs[n:2 * n], refs[2 * n:2 * n + 2],
        refs[2 * n + 2:3 * n + 2], refs[-1])
    k = taps_ref.shape[0]
    first = pl.program_id(1) == 0
    for (_, at, width), in_ref, halo_ref, out_ref in zip(parts, ins, halos,
                                                         outs):
        _stage(buf, at, in_ref, halo_ref, first)
        slab = _slab(width)
        for lo in range(0, width, slab):
            lanes = slice(at + lo, at + lo + slab)
            taps, bias = taps_ref[:, lanes], bias_ref[:, lanes]

            def block(r, carry, lanes=lanes, lo=lo, taps=taps, bias=bias,
                      out_ref=out_ref, slab=slab):
                r0 = pl.multiple_of(r * rc, rc)
                ext = buf[pl.ds(r0, rc + SUBLANES), lanes]
                z = _pre_activation(_shifted(ext, k), taps, bias)
                out_ref[pl.ds(r0, rc), lo:lo + slab] = (
                    z * _sigmoid(z)).astype(out_ref.dtype)
                return carry

            jax.lax.fori_loop(0, in_ref.shape[0] // rc, block, None)


def _ssm_taps_bwd_kernel(*refs, parts, rc):
    """The tiles from a sample's last to its first (the index maps). refs:
    the parts' tiles, the parts' rows before, taps, bias, the parts'
    cotangents, ``d xBC`` (rows, W), the sums (K + 1, 8, W) of ``d taps``
    and ``d bias``; scratch: a tile in f32 behind 8 rows, and the tile's
    ``dz`` before the first 8 rows of the tile after's."""
    n = len(parts)
    ins, halos, (taps_ref, bias_ref), douts, (dx_ref, dw_ref, buf, dz_buf) = (
        refs[:n], refs[n:2 * n], refs[2 * n:2 * n + 2],
        refs[2 * n + 2:3 * n + 2], refs[3 * n + 2:])
    k = taps_ref.shape[0]
    rows = dx_ref.shape[0]
    first = pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when(pl.program_id(1) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dz_buf[rows:] = jnp.zeros((SUBLANES, dz_buf.shape[1]), jnp.float32)

    for (_, at, width), in_ref, halo_ref, dout_ref in zip(parts, ins, halos,
                                                          douts):
        _stage(buf, at, in_ref, halo_ref, first)
        slab = _slab(width)
        for lo in range(0, width, slab):
            lanes = slice(at + lo, at + lo + slab)
            taps, bias = taps_ref[:, lanes], bias_ref[:, lanes]

            def dz_of(r, sums, lanes=lanes, lo=lo, taps=taps, bias=bias,
                      dout_ref=dout_ref, slab=slab):
                r0 = pl.multiple_of(r * rc, rc)
                shifted = _shifted(buf[pl.ds(r0, rc + SUBLANES), lanes], k)
                z = _pre_activation(shifted, taps, bias)
                s = _sigmoid(z)
                dz = dout_ref[pl.ds(r0, rc), lo:lo + slab].astype(
                    jnp.float32) * (s * (1.0 + z * (1.0 - s)))
                dz_buf[pl.ds(r0, rc), lanes] = dz
                return tuple(
                    [acc + _by_sublane(dz * shifted[k - 1 - j])
                     for j, acc in enumerate(sums[:k])]
                    + [sums[k] + _by_sublane(dz)])

            sums = jax.lax.fori_loop(
                0, rows // rc, dz_of,
                (jnp.zeros((SUBLANES, slab), jnp.float32),) * (k + 1))
            for j, acc in enumerate(sums):
                dw_ref[j, :, lanes] += acc

            def dx_of(r, carry, lanes=lanes, taps=taps):
                r0 = pl.multiple_of(r * rc, rc)
                ext = dz_buf[pl.ds(r0, rc + SUBLANES), lanes]
                dx = taps[k - 1:k] * ext[:rc]
                for back in range(1, k):
                    dx = dx + taps[k - 1 - back:k - back] * pltpu.roll(
                        ext, rc + SUBLANES - back, 0)[:rc]
                dx_ref[pl.ds(r0, rc), lanes] = dx.astype(dx_ref.dtype)
                return carry

            jax.lax.fori_loop(0, rows // rc, dx_of, None)
            dz_buf[rows:, lanes] = dz_buf[:SUBLANES, lanes]


def _taps_specs(shape, itemsize, parts, taps, reverse: bool):
    """(grid, the parts' tiles, the parts' rows before, taps, bias, a whole
    tile of ``xBC``, the tile's rows). ``reverse``: a sample's tiles from
    its last to its first."""
    b, t, _ = shape
    rows, halo = rows_tile(t, itemsize), halo_rows(itemsize)
    steps, lanes = t // rows, sum(width for _, _, width in parts)
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    tiles = [pl.BlockSpec((None, rows, width),
                          lambda n, i, c=lo // width: (n, at(i), c))
             for lo, _, width in parts]
    halos = [pl.BlockSpec(
        (None, halo, width),
        lambda n, i, c=lo // width: (n, jnp.maximum(
            at(i) * (rows // halo) - 1, 0), c))
        for lo, _, width in parts]
    return ((b, steps), tiles, halos,
            pl.BlockSpec((taps, lanes), lambda n, i: (0, 0)),
            pl.BlockSpec((1, lanes), lambda n, i: (0, 0)),
            pl.BlockSpec((None, rows, lanes), lambda n, i: (n, at(i), 0)),
            rows)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM)


@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _taps_fwd_call(zxbcdt, taps, bias, *, parts, interpret):
    b, t, _ = zxbcdt.shape
    itemsize = zxbcdt.dtype.itemsize
    grid, tiles, halos, taps_spec, bias_spec, _, rows = _taps_specs(
        zxbcdt.shape, itemsize, parts, taps.shape[0], False)
    with jax.named_scope(TAPS_SCOPE):
        return tuple(pl.pallas_call(
            functools.partial(_ssm_taps_fwd_kernel, parts=parts,
                              rc=_chunk(rows, CHUNK, itemsize)),
            grid=grid,
            in_specs=tiles + halos + [taps_spec, bias_spec],
            out_specs=[pl.BlockSpec((None, rows, width),
                                    lambda n, i: (n, i, 0))
                       for _, _, width in parts],
            out_shape=[jax.ShapeDtypeStruct((b, t, width), zxbcdt.dtype)
                       for _, _, width in parts],
            scratch_shapes=[pltpu.VMEM((SUBLANES + rows, taps.shape[1]),
                                       jnp.float32)],
            compiler_params=_params("parallel", "parallel"),
            interpret=interpret,
        )(*[zxbcdt] * (2 * len(parts)), taps, bias[None]))


@functools.partial(jax.jit, static_argnames=("parts", "interpret"))
def _taps_bwd_call(zxbcdt, taps, bias, douts, *, parts, interpret):
    """(d xBC (B, T, W), d taps (K, W), d bias (W,)), the sums in f32."""
    b, t, _ = zxbcdt.shape
    itemsize = zxbcdt.dtype.itemsize
    k, lanes = taps.shape
    grid, tiles, halos, taps_spec, bias_spec, whole, rows = _taps_specs(
        zxbcdt.shape, itemsize, parts, k, True)
    steps = grid[1]
    scratch = pltpu.VMEM((SUBLANES + rows, lanes), jnp.float32)
    with jax.named_scope(TAPS_SCOPE):
        dxbc, sums = pl.pallas_call(
            functools.partial(_ssm_taps_bwd_kernel, parts=parts,
                              rc=_chunk(rows, CHUNK, itemsize)),
            grid=grid,
            in_specs=tiles + halos + [taps_spec, bias_spec] + [
                pl.BlockSpec((None, rows, width),
                             lambda n, i: (n, steps - 1 - i, 0))
                for _, _, width in parts],
            out_specs=[whole,
                       pl.BlockSpec((None, k + 1, SUBLANES, lanes),
                                    lambda n, i: (n, 0, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((b, t, lanes), zxbcdt.dtype),
                       jax.ShapeDtypeStruct((b, k + 1, SUBLANES, lanes),
                                            jnp.float32)],
            scratch_shapes=[scratch, scratch],
            compiler_params=_params("parallel", "arbitrary"),
            interpret=interpret,
        )(*[zxbcdt] * (2 * len(parts)), taps, bias[None], *douts)
        sums = jnp.sum(sums, axis=(0, 2))
        return dxbc, sums[:k], sums[k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def taps_silu(zxbcdt, taps, bias, before: int, widths: Tuple[int, ...],
              interpret: bool = False):
    """``causal_taps_silu`` of the ``sum(widths)`` lanes of ``zxbcdt`` (B,
    T, .) from lane ``before`` on, read where they lie, as one array a part
    of ``widths``; where :func:`taps_fit`. taps: (K, W) f32, bias: (W,)
    f32. Gradient residuals: the operands."""
    return _taps_fwd_call(zxbcdt, taps, bias, parts=_parts(before, widths),
                          interpret=interpret)


def _taps_vjp_fwd(zxbcdt, taps, bias, before, widths, interpret):
    return taps_silu(zxbcdt, taps, bias, before, widths, interpret), (
        zxbcdt, taps, bias)


def _taps_vjp_bwd(before, widths, interpret, res, douts):
    zxbcdt, taps, bias = res
    dxbc, dtaps, dbias = _taps_bwd_call(
        zxbcdt, taps, bias, douts, parts=_parts(before, widths),
        interpret=interpret)
    # the other lanes are not this pass's: one pad of noughts, which XLA
    # fuses into the sum with what their own readers hand back
    after = zxbcdt.shape[2] - before - dxbc.shape[2]
    return (jnp.pad(dxbc, ((0, 0), (0, 0), (before, after))),
            dtaps.astype(taps.dtype), dbias.astype(bias.dtype))


taps_silu.defvjp(_taps_vjp_fwd, _taps_vjp_bwd)


# ---------------------------------------------------------------------------
# The gate and the group norm
# ---------------------------------------------------------------------------

def _group_sums(tiles, ones):
    """The sum over a group's lanes, on every lane of a tile: the group's
    lane tiles added, then a product with ones."""
    return _summed(functools.reduce(jnp.add, tiles), ones)


def _gated(y_ref, z_ref, rows, lanes):
    """A block's ``y``, ``z``, ``sigmoid(z)`` and ``silu(z)`` in f32."""
    y = y_ref[rows, lanes].astype(jnp.float32)
    z = z_ref[rows, lanes].astype(jnp.float32)
    s = _sigmoid(z)
    return y, z, s, z * s


def _tiles_of(v):
    return [v[:, lo:lo + LANES] for lo in range(0, v.shape[1], LANES)]


def _inverse_rms(gated, ones, eps):
    """``rsqrt(mean over the group's lanes of gated^2 + eps)`` on every
    lane of a tile; gated: the group's lane tiles."""
    return jax.lax.rsqrt(_group_sums([g * g for g in gated], ones)
                         * (1.0 / (len(gated) * LANES)) + eps)


def _ssm_gate_norm_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, group,
                              eps, rc):
    """A tile's groups in turn, ``rc`` rows at a time. y, z, out: (rows,
    W); scale: (1, W) f32."""
    ones = jnp.ones((LANES, LANES), y_ref.dtype)
    for lo in range(0, y_ref.shape[1], group):
        lanes = slice(lo, lo + group)

        def block(r, carry, lanes=lanes, lo=lo):
            rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
            y, _, _, silu = _gated(y_ref, z_ref, rows, lanes)
            gated = _tiles_of(y * silu)
            inv = _inverse_rms(gated, ones, eps)
            for at, g in enumerate(gated):
                tile = slice(lo + at * LANES, lo + (at + 1) * LANES)
                out_ref[rows, tile] = (g * inv * scale_ref[:, tile]).astype(
                    out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, y_ref.shape[0] // rc, block, None)


def _ssm_gate_norm_bwd_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref,
                              dz_ref, ds_ref, *, group, eps, rc):
    """Operands as the forward's, with ``dout`` like ``out``; dy, dz like
    y, z; ds: (8, W) f32, the sample's sum of ``dout`` times the normed
    value, which stays in VMEM along the tokens."""
    ones = jnp.ones((LANES, LANES), y_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    for lo in range(0, y_ref.shape[1], group):
        lanes = slice(lo, lo + group)

        def block(r, sums, lanes=lanes, lo=lo):
            rows = pl.ds(pl.multiple_of(r * rc, rc), rc)
            y, z, s, silu = _gated(y_ref, z_ref, rows, lanes)
            gated = _tiles_of(y * silu)
            inv = _inverse_rms(gated, ones, eps)
            normed = [g * inv for g in gated]
            douts = _tiles_of(dout_ref[rows, lanes].astype(jnp.float32))
            scaled = [d * scale_ref[:, lo + at * LANES:lo + (at + 1) * LANES]
                      for at, d in enumerate(douts)]
            mean = _group_sums([g * x for g, x in zip(scaled, normed)],
                               ones) * (1.0 / group)
            dgated = jnp.concatenate(
                [inv * (g - x * mean) for g, x in zip(scaled, normed)],
                axis=1)
            dy_ref[rows, lanes] = (dgated * silu).astype(dy_ref.dtype)
            dz_ref[rows, lanes] = (
                dgated * y * (s * (1.0 + z * (1.0 - s)))).astype(dz_ref.dtype)
            return sums + _by_sublane(jnp.concatenate(
                [d * x for d, x in zip(douts, normed)], axis=1))

        ds_ref[:, lanes] += jax.lax.fori_loop(
            0, y_ref.shape[0] // rc, block,
            jnp.zeros((SUBLANES, group), jnp.float32))


def _gate_norm_specs(shape, itemsize):
    """(grid, a tile of ``y`` or of ``z`` where it lies (the first column
    block of its array), the scale, the tile's rows)."""
    b, t, width = shape
    rows = rows_tile(t, itemsize)
    return ((b, t // rows),
            pl.BlockSpec((None, rows, width), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, width), lambda n, i: (0, 0)), rows)


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "interpret"))
def _gate_norm_fwd_call(y, zxbcdt, scale, *, groups, eps, interpret):
    itemsize = y.dtype.itemsize
    grid, tile, scale_spec, rows = _gate_norm_specs(y.shape, itemsize)
    with jax.named_scope(GATE_NORM_SCOPE):
        return pl.pallas_call(
            functools.partial(_ssm_gate_norm_fwd_kernel,
                              group=y.shape[2] // groups, eps=eps,
                              rc=_chunk(rows, NORM_CHUNK, itemsize)),
            grid=grid,
            in_specs=[tile, tile, scale_spec],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            compiler_params=_params("parallel", "parallel"),
            interpret=interpret,
        )(y, zxbcdt, scale[None])


@functools.partial(jax.jit,
                   static_argnames=("groups", "eps", "interpret"))
def _gate_norm_bwd_call(y, zxbcdt, scale, dout, *, groups, eps, interpret):
    """(d y, d z (B, T, W), d scale (W,) f32)."""
    itemsize = y.dtype.itemsize
    b, _, width = y.shape
    grid, tile, scale_spec, rows = _gate_norm_specs(y.shape, itemsize)
    like = jax.ShapeDtypeStruct(y.shape, y.dtype)
    with jax.named_scope(GATE_NORM_SCOPE):
        dy, dz, ds = pl.pallas_call(
            functools.partial(_ssm_gate_norm_bwd_kernel,
                              group=width // groups, eps=eps,
                              rc=_chunk(rows, NORM_CHUNK, itemsize)),
            grid=grid,
            in_specs=[tile, tile, scale_spec, tile],
            out_specs=[tile, tile,
                       pl.BlockSpec((None, SUBLANES, width),
                                    lambda n, i: (n, 0, 0))],
            out_shape=[like, like, jax.ShapeDtypeStruct(
                (b, SUBLANES, width), jnp.float32)],
            compiler_params=_params("parallel", "arbitrary"),
            interpret=interpret,
        )(y, zxbcdt, scale[None], dout)
        return dy, dz, jnp.sum(ds, axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def gate_norm(y, zxbcdt, scale, groups: int, eps: float,
              interpret: bool = False):
    """``gated_group_norm(y, z, scale, groups, eps)`` with ``z`` the first
    ``y.shape[2]`` lanes of ``zxbcdt``, read where they lie; where
    :func:`gate_norm_fit`. scale: (W,) f32. Gradient residuals: the
    operands."""
    return _gate_norm_fwd_call(y, zxbcdt, scale, groups=groups, eps=eps,
                               interpret=interpret)


def _gate_norm_vjp_fwd(y, zxbcdt, scale, groups, eps, interpret):
    return gate_norm(y, zxbcdt, scale, groups, eps, interpret), (
        y, zxbcdt, scale)


def _gate_norm_vjp_bwd(groups, eps, interpret, res, dout):
    y, zxbcdt, scale = res
    dy, dz, dscale = _gate_norm_bwd_call(y, zxbcdt, scale, dout,
                                         groups=groups, eps=eps,
                                         interpret=interpret)
    after = zxbcdt.shape[2] - dz.shape[2]
    return (dy, jnp.pad(dz, ((0, 0), (0, 0), (0, after))),
            dscale.astype(scale.dtype))


gate_norm.defvjp(_gate_norm_vjp_fwd, _gate_norm_vjp_bwd)
