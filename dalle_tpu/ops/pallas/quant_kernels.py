"""Pallas TPU kernels for block-wise 8-bit quantization.

Device-side replacement for the bitsandbytes CUDA kernels the reference's
8-bit LAMB calls (``lib/training/lamb_8bit.py:181-242``): on TPU the
quantize step becomes a VPU kernel over (rows, block) tiles.

Design notes (TPU-first):
- Nearest-codebook lookup is reformulated as *threshold counting*:
  ``code = sum_k [x > t_k]`` where ``t_k`` are the 255 midpoints between
  consecutive codebook entries. This avoids gathers (weak on the TPU
  vector unit) in favor of 255 vectorized compares + adds, which the VPU
  eats at 8x128 lanes per cycle.
- Dequantization stays in plain XLA (``ops.quant.dequantize_blockwise``,
  a 256-entry ``jnp.take``); the hot direction is quantize (runs on every
  optimizer step / every wire compression) and is what this module covers.
- Tiles are (8, block) float32 — block must be a multiple of 128 (the
  reference block of 4096 = 32 * 128 fits natively).

Interpret mode makes the same kernel run in CI on CPU (tests/conftest.py
forces the cpu platform).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dalle_tpu.ops.quant import codebook_midpoints

ROWS_PER_TILE = 8


@functools.lru_cache(maxsize=8)
def _thresholds(signed: bool) -> np.ndarray:
    # The shared float32 decision boundaries (ops.quant.codebook_midpoints),
    # padded to 256 lanes with +inf so the padded threshold never counts.
    mids = codebook_midpoints(signed)
    return np.concatenate([mids, [np.inf]]).astype(np.float32)


def _quant_kernel(x_ref, thr_ref, codes_ref, absmax_ref):
    x = x_ref[:]                               # (rows, block) f32
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax, 1.0)
    normed = x / scale
    # code = number of thresholds strictly below the value. Thresholds live
    # in SMEM; thr_ref[k] is a scalar load with a dynamic index, which
    # Mosaic supports (vector dynamic_slice is not lowerable on TPU).
    code = jnp.zeros(x.shape, jnp.int32)

    def body(k, code):
        return code + (normed > thr_ref[k]).astype(jnp.int32)

    code = jax.lax.fori_loop(0, 255, body, code)
    codes_ref[:] = code.astype(jnp.uint8)
    absmax_ref[:] = absmax


def quantize_blocks_pallas(blocks: jax.Array, signed: bool = True,
                           interpret: bool = False):
    """(codes uint8 (n_blocks, block), absmax f32 (n_blocks, 1)) of rows
    that are already blocked (``ops.quant.to_blocks``) — the kernel half
    of ops.quant.quantize_blockwise, which wraps the result in a
    Quantized. Taking blocked rows lets a sharded caller run it on each
    device's own rows. The block must be a multiple of 128."""
    n_blocks, block_size = blocks.shape
    if block_size % 128:
        raise ValueError("block_size must be a multiple of 128")
    # pad rows up to a tile multiple
    rows = -(-n_blocks // ROWS_PER_TILE) * ROWS_PER_TILE
    blocks = jnp.pad(blocks.astype(jnp.float32),
                     ((0, rows - n_blocks), (0, 0)))

    thr = jnp.asarray(_thresholds(signed))
    grid = (rows // ROWS_PER_TILE,)
    codes, absmax = pl.pallas_call(
        _quant_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, block_size), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, block_size), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((ROWS_PER_TILE, block_size), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(blocks, thr)
    return codes[:n_blocks], absmax[:n_blocks]


# -- linear (wire) u8 quantizer ------------------------------------------

WIRE_QBLOCK = 256  # the wire codec's block (compression._QBLOCK) = 2 lanes


def _to_u8(q):
    """Exact small non-negative integers in f32 -> uint8, by way of int32:
    Mosaic has no direct f32 -> u8 cast ("Unsupported cast")."""
    return q.astype(jnp.int32).astype(jnp.uint8)


def _wire_quant_kernel(x_ref, d_ref, codes_ref, scale_ref):
    """Blockwise symmetric uniform u8 (the swarm wire codec): per 256-elem
    block, scale = absmax/127, code = clip(rint(x/scale), -128, 127)+128.
    All IEEE f32 elementwise VPU ops in the same order as the host numpy
    and XLA paths (swarm/compression.py, swarm/device_codec.py), so the
    three produce byte-identical codes and scales — including at
    round-half-even ties. The 127 divisor arrives as a runtime scalar
    (SMEM) so no compiler can strength-reduce the divide into a
    reciprocal multiply (1 ulp off for ~3% of absmax values — enough to
    flip wire bytes; see device_codec's parity note)."""
    x = x_ref[:]                               # (rows, WIRE_QBLOCK) f32
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / d_ref[0]
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint(x / safe), -128.0, 127.0) + 128.0
    codes_ref[:] = _to_u8(q)
    scale_ref[:] = scale


def wire_quantize_u8_pallas(x: jax.Array, interpret: bool = False):
    """(codes uint8 (n,), scales f32 (ceil(n/256),)) in the swarm wire
    format's block geometry — the device encode half of
    swarm/device_codec.py, as a VPU kernel. The tail block is zero-padded
    exactly like the host codec, so its scale and codes match."""
    return _wire_quantize_pallas(x, WIRE_QBLOCK, 127.0,
                                 interpret=interpret)


# -- linear (wire) u4 quantizer ------------------------------------------

WIRE_QBLOCK4 = 1024  # the u4 wire block (compression._QBLOCK4) = 8 lanes


def _wire_quant4_kernel(x_ref, d_ref, codes_ref, scale_ref):
    """The u4 twin of ``_wire_quant_kernel``: per 1024-elem block,
    scale = absmax/7, code = clip(rint(x/scale), -8, 7) + 8 — same IEEE
    op order as the host/XLA u4 paths (byte parity), same runtime-scalar
    divisor rule. Emits UNPACKED codes in [0, 15]; nibble packing is a
    pure byte shuffle the caller does in XLA (identical either way)."""
    x = x_ref[:]                               # (rows, WIRE_QBLOCK4) f32
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / d_ref[0]
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.rint(x / safe), -8.0, 7.0) + 8.0
    codes_ref[:] = _to_u8(q)
    scale_ref[:] = scale


def wire_quantize_u4_pallas(x: jax.Array, interpret: bool = False):
    """(unpacked codes uint8 (n,) in [0, 15], scales f32
    (ceil(n/1024),)) — the device encode half of the u4 wire codec as a
    VPU kernel; swarm/device_codec.py packs the nibble pairs."""
    return _wire_quantize_pallas(x, WIRE_QBLOCK4, 7.0,
                                 interpret=interpret)


def _wire_quantize_pallas(x: jax.Array, block: int, divisor: float,
                          interpret: bool = False):
    """Shared launch shape of the two wire quantizers: block the flat
    vector, pad rows to a tile multiple (padded rows are all-zero:
    scale 0, zero code, sliced off), run the per-width kernel selected
    by ``block``."""
    kernel = (_wire_quant_kernel if block == WIRE_QBLOCK
              else _wire_quant4_kernel)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    n_blocks = -(-n // block)
    rows = -(-n_blocks // ROWS_PER_TILE) * ROWS_PER_TILE
    blocks = jnp.pad(flat, (0, rows * block - n)).reshape(rows, block)
    codes, scales = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, block), jnp.uint8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        grid=(rows // ROWS_PER_TILE,),
        in_specs=[
            pl.BlockSpec((ROWS_PER_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((ROWS_PER_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)),
        ),
        interpret=interpret,
    )(blocks, jnp.full((1,), divisor, jnp.float32))
    return (codes[:n_blocks].reshape(-1)[:n],
            scales[:n_blocks].reshape(-1))
