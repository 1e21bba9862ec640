"""Block-wise 8-bit quantization with a dynamic (nonlinear) codebook.

TPU-native equivalent of the bitsandbytes ``quantize_blockwise`` /
``dequantize_blockwise`` kernels the reference's 8-bit LAMB depends on
(``lib/training/lamb_8bit.py:7,181-242`` of learning-at-home/dalle). Values
are grouped into blocks of ``block_size`` (reference uses 4096,
``lamb_8bit.py:49``), each block is scaled by its absmax, and the scaled
values are rounded to the nearest entry of a 256-entry *dynamic* codebook
(dynamic tree quantization from "8-bit Optimizers via Block-wise
Quantization", Dettmers et al. 2021 — see PAPERS.md): a sign bit, a unary
exponent that eats leading bits, and a linear fraction in the remaining
bits, giving fine resolution near zero and full range up to 1.

On TPU these run as XLA ops over (n_blocks, block_size) arrays — the
reference's chunked CPU loop (``lamb_8bit.py:202-249``, a host-RAM
workaround) is unnecessary. The quantize direction (the hot one — it runs
per optimizer step and per wire compression) has a Pallas VPU kernel in
:mod:`dalle_tpu.ops.pallas.quant_kernels`, used automatically on TPU;
dequantize is a 256-entry ``jnp.take`` XLA fuses fine.

Tie-breaking contract: a value exactly on the midpoint between two codebook
entries maps to the LOWER code. Both the XLA path and the Pallas kernel
derive their decision boundaries from the same float32
:func:`codebook_midpoints`, so they agree byte-for-byte.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

DEFAULT_BLOCK = 4096


@functools.lru_cache(maxsize=8)
def dynamic_codebook(signed: bool = True) -> np.ndarray:
    """256-entry sorted codebook in [-1, 1] (signed) or [0, 1] (unsigned).

    Dynamic tree layout: for exponent level e (0 = largest magnitudes), the
    magnitudes are ``10**-e * linspace`` with ``2**(data_bits - 1 - e)``
    linear steps — more exponent range for small values, more fraction
    precision for large ones.
    """
    data_bits = 7 if signed else 8
    mags = [0.0]
    for e in range(data_bits):
        n = 2 ** (data_bits - 1 - e)
        if n == 0:
            break
        frac = (np.arange(n) + 1.0) / n           # (0, 1]
        mags.extend((10.0 ** -e) * frac)
    mags = np.asarray(sorted(set(mags)), dtype=np.float64)
    if signed:
        vals = np.concatenate([-mags[::-1], mags[1:]])
    else:
        vals = mags
    # Fit to exactly 256 entries: pad with interpolated midpoints or trim
    # the densest region near zero.
    # Work in float32 from here so dedup/padding reflect the stored dtype.
    vals = np.unique(vals.astype(np.float32))
    while vals.size > 256:
        # drop the entry closest to zero (excluding zero itself)
        nz = np.nonzero(vals)[0]
        drop = nz[np.argmin(np.abs(vals[nz]))]
        vals = np.delete(vals, drop)
    while vals.size < 256:
        # insert a midpoint into the widest gap
        gaps = np.diff(vals)
        i = int(np.argmax(gaps))
        mid = np.float32(0.5 * (vals[i] + vals[i + 1]))
        if mid == vals[i] or mid == vals[i + 1]:  # float32 collapse
            break
        vals = np.insert(vals, i + 1, mid)
    assert vals.size == 256, vals.size
    assert (np.diff(vals) > 0).all()
    return vals


@functools.lru_cache(maxsize=8)
def codebook_midpoints(signed: bool = True) -> np.ndarray:
    """255 float32 decision boundaries between consecutive codebook entries.

    ``code(v) = #{k : v > mid_k}`` — shared by the XLA and Pallas paths so
    they are byte-identical, including at ties.
    """
    cb = dynamic_codebook(signed)
    return (0.5 * (cb[:-1] + cb[1:])).astype(np.float32)


class Quantized(flax.struct.PyTreeNode):
    """Block-quantized tensor: uint8 codes + per-block absmax + shape."""

    codes: jax.Array                    # (n_blocks, block) uint8
    absmax: jax.Array                   # (n_blocks, 1) float32
    shape: Tuple[int, ...] = flax.struct.field(pytree_node=False)
    signed: bool = flax.struct.field(pytree_node=False, default=True)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape))


def to_blocks(x: jax.Array, block_size: int) -> jax.Array:
    """(n_blocks, block_size) float32 blocking of ``x``, zero-padded at the
    tail. Shared by the XLA path and the Pallas wrapper so the two prologues
    cannot drift (their byte-parity contract depends on it)."""
    flat = x.reshape(-1).astype(jnp.float32)
    n_blocks = -(-flat.shape[0] // block_size)
    flat = jnp.pad(flat, (0, n_blocks * block_size - flat.shape[0]))
    return flat.reshape(n_blocks, block_size)


def _nearest_code(normed: jax.Array, signed: bool) -> jax.Array:
    """Nearest codebook index = count of midpoints strictly below the value
    (searchsorted-left over the shared float32 midpoints)."""
    mids = jnp.asarray(codebook_midpoints(signed))
    return jnp.searchsorted(mids, normed, side="left").astype(jnp.uint8)


def blocks_spec(mesh, n_blocks: int) -> P:
    """How a leaf's (n_blocks, ...) quantized codes/absmax lie on the mesh:
    rows over fsdp when they divide, else replicated. ONE rule for the
    optimizer-state placement (parallel/sharding.py) and the per-shard
    quantize kernel below, which must agree."""
    fsdp = mesh.shape.get("fsdp", 1) if mesh is not None else 1
    return P("fsdp") if fsdp > 1 and n_blocks % fsdp == 0 else P()


def quantize_blockwise(x: jax.Array, block_size: int = DEFAULT_BLOCK,
                       signed: bool = True,
                       use_pallas: Optional[bool] = None,
                       mesh=None, interpret: bool = False) -> Quantized:
    """Block-quantize ``x``. ``use_pallas=None`` auto-selects the Pallas VPU
    kernel on TPU when the block size tiles lanes (multiple of 128). With a
    ``mesh`` of more than one device the kernel runs per shard of the
    block rows (:func:`blocks_spec`); the blocking prologue stays in XLA."""
    shape = tuple(x.shape)
    if use_pallas is None:
        use_pallas = (jax.default_backend() == "tpu"
                      and block_size % 128 == 0)
    blocks = to_blocks(x, block_size)
    if use_pallas:
        from dalle_tpu.ops.pallas.quant_kernels import quantize_blocks_pallas
        from dalle_tpu.parallel.mesh import per_shard
        spec = blocks_spec(mesh, blocks.shape[0])
        codes, absmax = per_shard(
            functools.partial(quantize_blocks_pallas, signed=signed,
                              interpret=interpret),
            mesh, (spec,), (spec, spec))(blocks)
        return Quantized(codes=codes, absmax=absmax, shape=shape,
                         signed=signed)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax, 1.0)
    normed = blocks / scale
    codes = _nearest_code(normed, signed)
    return Quantized(codes=codes, absmax=absmax, shape=shape, signed=signed)


def _select_tree_lookup(codes: jax.Array, codebook: np.ndarray) -> jax.Array:
    """Gather-free 256-entry table lookup as a fused binary select tree.

    A 256-entry dynamic gather runs at ~20M elem/s on TPU (it dominated the
    optimizer-apply profile at 79%); 255 fused jnp.where selects keyed on the
    code's bits run on the VPU at ~5x that, and are byte-exact."""

    def tree(bits: jax.Array, cb: np.ndarray, bitpos: int) -> jax.Array:
        if cb.size == 1:
            return jnp.full(bits.shape, np.float32(cb[0]), jnp.float32)
        half = cb.size // 2
        bit = ((bits >> bitpos) & 1).astype(bool)
        return jnp.where(bit, tree(bits, cb[half:], bitpos - 1),
                         tree(bits, cb[:half], bitpos - 1))

    return tree(codes.astype(jnp.int32), codebook.astype(np.float32), 7)


def dequantize_blockwise(q: Quantized,
                         use_tree: Optional[bool] = None) -> jax.Array:
    """Dequantize. ``use_tree=None`` auto-selects the select-tree lookup on
    TPU (dynamic gathers are pathologically slow there); other backends use
    the plain gather. Both produce identical bytes."""
    if use_tree is None:
        use_tree = jax.default_backend() == "tpu"
    codebook = dynamic_codebook(q.signed)
    if use_tree:
        vals = _select_tree_lookup(q.codes, codebook) * q.absmax
    else:
        vals = jnp.asarray(codebook)[q.codes.astype(jnp.int32)] * q.absmax
    return vals.reshape(-1)[: q.size].reshape(q.shape)
