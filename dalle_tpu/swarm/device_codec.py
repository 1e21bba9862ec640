"""Device-side wire codec: quantize/dequantize swarm gradients on the
accelerator, leave the host to frame, sign, and ship bytes.

VERDICT r5 weak #1: at the flagship's 502 MB gradient payload an N=4
all-reduce epoch burned 20.1 s encoding + 13.8 s decoding in pure host
numpy while the TPU idled. The codec math — blockwise symmetric u8
quantization and f16 casts — is exactly the elementwise work accelerators
exist for (EQuARX and 8-bit Optimizers both run the quantized-collective
codec on the device, PAPERS.md), so this module runs it as jitted JAX
programs: the quantize direction gets a Pallas VPU kernel on TPU
(:func:`dalle_tpu.ops.pallas.quant_kernels.wire_quantize_u8_pallas`,
same family as the existing dynamic-codebook kernel) with an XLA
fallback everywhere else (CPU peers, CI), and the dequantize direction
is a multiply XLA fuses fine on every backend.

**Byte compatibility is the contract.** Every function here produces and
consumes the *existing* wire format of :mod:`dalle_tpu.swarm.compression`
— big-endian u32 element count, ceil(n/256) native-endian f32 scales,
n u8 codes (code 128 = zero, scale = absmax/127) for UNIFORM8BIT;
IEEE-f16 payloads for FLOAT16 — so device-codec peers interoperate on
the wire with host-codec peers chunk by chunk. Parity is exact, not
approximate: both sides use the same IEEE f32 divide / round-half-even /
clip sequence on the same block geometry, so codes and scales agree
byte-for-byte and f16 payloads are bit-identical
(tests/test_device_codec.py pins both directions).

**Whole-part encode.** :func:`encode_part` quantizes an entire all-reduce
part in ONE device call and returns an :class:`EncodedPart` holding the
packed u8/scale buffers (still on device — dispatch is async). Only those
packed buffers ever cross to the host: :func:`part_payload` pulls them
once and then frames each CHUNK_ELEMS wire chunk by pure byte slicing
(chunk boundaries are multiples of the 256-element quant block, so the
part-level blocks ARE the chunk-level blocks), and :func:`part_decode`
dequantizes the part's own lossy bytes on device for the gather phase's
local apply. The host never touches a float of codec math.

Chunk-order independence is what lets the r19 pipelined butterfly
(``pipeline_hops``) reorder this work freely: a part is quantized in
ONE device call whose result every chunk producer shares (the
``lazy_part_enc`` memo in allreduce.py), ``part_payload`` /
``part_decode`` are pure slices of that one encode, and
:func:`fused_accumulate` folds each sender's chunks into the
accumulator only once that sender's contribution is COMPLETE — so
chunks arriving out of order across parts and legs can never change
a byte of codec output, only when it is produced. (Accumulation
ORDER across senders remains arrival-order, as before the pipeline —
recorded per round by the r14 audit transcript and replayed in that
recorded order.)
"""

from __future__ import annotations

import functools
import struct
import threading
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dalle_tpu.swarm import compression

_QBLOCK = compression._QBLOCK
_QBLOCK4 = compression._QBLOCK4

_F16_MIN = float(np.finfo(np.float16).min)
_F16_MAX = float(np.finfo(np.float16).max)


def resolve_backend(name: Optional[str]) -> str:
    """Map a config value to a concrete codec backend. ``auto`` picks
    ``device`` when this process drives an accelerator (the codec then
    runs where the gradients already live) and ``host`` on CPU-only
    peers, where jitted XLA still wins over numpy but a volunteer's
    aux/client processes shouldn't pay jit warmup for it by default."""
    if name in (None, "auto"):
        return "device" if jax.default_backend() == "tpu" else "host"
    if name not in ("host", "device"):
        raise ValueError(f"unknown wire codec backend {name!r}")
    return name


# -- jitted codec programs (XLA path) ------------------------------------
# Bit-parity note: the op sequence mirrors compression.compress_u8 /
# decompress_u8 exactly — absmax, scale = absmax/127, safe = where(>0),
# divide, rint (round-half-even), clip, +128 — all IEEE f32 elementwise,
# so XLA, Pallas and numpy produce identical codes/scales for identical
# input bytes. Do not "simplify" the order (e.g. folding /127 into the
# divide): it changes rounding and breaks cross-peer wire parity.
#
# The 127 (and the u4 path's 7) divisor is passed as a RUNTIME operand,
# never a literal: XLA's simplifier strength-reduces divide-by-constant
# into multiply-by-reciprocal, which is 1 ulp off the IEEE divide for
# ~3% of absmax values — enough to flip wire scale bytes vs the host
# codec (caught by the parity tests at n=2^16). A traced operand keeps
# the true divide.

_D127: Optional[jax.Array] = None
_D7: Optional[jax.Array] = None


def _d127() -> jax.Array:
    global _D127
    if _D127 is None:
        _D127 = jnp.asarray(np.float32(127.0))
    return _D127


def _d7() -> jax.Array:
    global _D7
    if _D7 is None:
        _D7 = jnp.asarray(np.float32(7.0))
    return _D7


@jax.jit
def _enc_u8_xla_impl(flat: jax.Array, d127: jax.Array):
    n = flat.shape[0]
    n_blocks = -(-n // _QBLOCK)
    blocks = jnp.pad(flat, (0, n_blocks * _QBLOCK - n)).reshape(
        n_blocks, _QBLOCK)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scales = absmax / d127
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.rint(blocks / safe[:, None]), -128.0, 127.0) + 128.0
    return q.astype(jnp.uint8).reshape(-1)[:n], scales


def _enc_u8_xla(flat: jax.Array):
    return _enc_u8_xla_impl(flat, _d127())


@jax.jit
def _dec_u8(codes: jax.Array, scales: jax.Array) -> jax.Array:
    n = codes.shape[0]
    n_blocks = scales.shape[0]
    c = jnp.pad(codes, (0, n_blocks * _QBLOCK - n)).astype(jnp.float32)
    c = c - 128.0
    out = c.reshape(n_blocks, _QBLOCK) * scales[:, None]
    return out.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnums=2)
def _enc_u4_impl(flat: jax.Array, d7: jax.Array, n: int):
    """(packed codes (ceil(n/2),) u8 — two per byte, low nibble first —
    scales (ceil(n/1024),) f32). Same IEEE op order as the host
    compress_u4 and the Pallas u4 kernel; an odd tail packs nibble 0
    exactly like the host codec."""
    n_blocks = -(-n // _QBLOCK4)
    blocks = jnp.pad(flat, (0, n_blocks * _QBLOCK4 - n)).reshape(
        n_blocks, _QBLOCK4)
    absmax = jnp.max(jnp.abs(blocks), axis=1)
    scales = absmax / d7
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.rint(blocks / safe[:, None]), -8.0, 7.0) + 8.0
    codes = q.astype(jnp.uint8).reshape(-1)[:n]
    codes = jnp.pad(codes, (0, n % 2))
    packed = codes[0::2] | (codes[1::2] << 4)
    return packed, scales


def _enc_u4_xla(flat: jax.Array):
    return _enc_u4_impl(flat, _d7(), flat.shape[0])


@functools.partial(jax.jit, static_argnums=2)
def _dec_u4(packed: jax.Array, scales: jax.Array, n: int) -> jax.Array:
    n_blocks = scales.shape[0]
    codes = jnp.stack([packed & 0x0F, packed >> 4], axis=1).reshape(-1)
    c = jnp.pad(codes[:n], (0, n_blocks * _QBLOCK4 - n)).astype(
        jnp.float32)
    c = c - 8.0
    out = c.reshape(n_blocks, _QBLOCK4) * scales[:, None]
    return out.reshape(-1)[:n]


@jax.jit
def _enc_f16(flat: jax.Array) -> jax.Array:
    return jnp.clip(flat, _F16_MIN, _F16_MAX).astype(jnp.float16)


@jax.jit
def _dec_f16(h: jax.Array) -> jax.Array:
    return h.astype(jnp.float32)


@jax.jit
def _concat_f32(leaves):
    return jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])


def _as_flat_f32(x) -> jax.Array:
    if not isinstance(x, jax.Array):
        x = jnp.asarray(np.asarray(x))
    return x.reshape(-1).astype(jnp.float32)


def _encode_u8(flat: jax.Array):
    """(codes (n,) u8, scales (nblocks,) f32) — Pallas VPU kernel on TPU,
    XLA elsewhere. Both derive from the same op sequence, so the choice
    never changes wire bytes."""
    if jax.default_backend() == "tpu" and flat.shape[0] > 0:
        from dalle_tpu.ops.pallas.quant_kernels import \
            wire_quantize_u8_pallas
        return wire_quantize_u8_pallas(flat)
    return _enc_u8_xla(flat)


@jax.jit
def _pack_nibbles(codes: jax.Array) -> jax.Array:
    padded = jnp.pad(codes, (0, codes.shape[0] % 2))
    return padded[0::2] | (padded[1::2] << 4)


def _encode_u4(flat: jax.Array):
    """(packed codes (ceil(n/2),) u8, scales (ceil(n/1024),) f32) —
    Pallas VPU quantize + XLA nibble pack on TPU, one XLA program
    elsewhere; wire bytes identical either way."""
    if jax.default_backend() == "tpu" and flat.shape[0] > 0:
        from dalle_tpu.ops.pallas.quant_kernels import \
            wire_quantize_u4_pallas
        codes, scales = wire_quantize_u4_pallas(flat)
        return _pack_nibbles(codes), scales
    return _enc_u4_xla(flat)


def flatten_device(tensors: Sequence) -> jax.Array:
    """Device-side flatten_tensors: one jitted concat, no host pull.
    Accepts a mix of device and host arrays (host leaves are pushed).

    The flat vector lands on ONE device. A peer that trains over several
    chips hands over gradients replicated or sharded across its mesh, but
    the wire codec feeds one host NIC: every codec program downstream
    (slice, quantize kernel, dequantize, accumulate) is a single-device
    program, which is also the only way the Mosaic quantizers can run
    outside shard_map."""
    leaves = [jnp.asarray(np.asarray(t)) if not isinstance(t, jax.Array)
              else t for t in tensors]
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    flat = _concat_f32(leaves)
    if len(flat.sharding.device_set) > 1:
        flat = jax.device_put(flat, min(flat.sharding.device_set,
                                        key=lambda d: d.id))
    return flat


# -- single-buffer wire codec (registry entries) -------------------------

def compress(x, codec: int) -> bytes:
    """Device twin of :func:`compression.compress`: same signature, same
    bytes; ``x`` may be a device array (no host pull of the floats) or a
    host array (pushed once)."""
    if codec == compression.NONE:
        return np.asarray(x, np.float32).tobytes()
    flat = _as_flat_f32(x)
    if codec == compression.FLOAT16:
        return np.asarray(_enc_f16(flat)).tobytes()
    if codec == compression.UNIFORM8BIT:
        codes, scales = _encode_u8(flat)
        codes_np, scales_np = jax.device_get((codes, scales))
        return (struct.pack(">I", codes_np.size)
                + scales_np.astype(np.float32, copy=False).tobytes()
                + codes_np.tobytes())
    if codec == compression.UNIFORM4BIT:
        packed, scales = _encode_u4(flat)
        packed_np, scales_np = jax.device_get((packed, scales))
        return (struct.pack(">I", flat.shape[0])
                + scales_np.astype(np.float32, copy=False).tobytes()
                + packed_np.tobytes())
    raise ValueError(f"unknown codec {codec}")


def decompress(buf: bytes, codec: int, n: int) -> np.ndarray:
    """Device twin of :func:`compression.decompress`: parses the wire
    header on the host, dequantizes on device, returns host f32."""
    if codec == compression.NONE:
        return np.frombuffer(buf, np.float32, count=n).copy()
    if codec == compression.FLOAT16:
        h = np.frombuffer(buf, np.float16, count=n)
        return np.asarray(_dec_f16(jnp.asarray(h)))
    if codec == compression.UNIFORM8BIT:
        (n_hdr,) = struct.unpack(">I", buf[:4])
        n_blocks = (n_hdr + _QBLOCK - 1) // _QBLOCK
        scales = np.frombuffer(buf, np.float32, count=n_blocks, offset=4)
        codes = np.frombuffer(buf, np.uint8, count=n_hdr,
                              offset=4 + 4 * n_blocks)
        out = np.asarray(_dec_u8(jnp.asarray(codes), jnp.asarray(scales)))
        if out.size != n:
            raise ValueError(f"decoded {out.size} elements, expected {n}")
        return out
    if codec == compression.UNIFORM4BIT:
        (n_hdr,) = struct.unpack(">I", buf[:4])
        n_blocks = (n_hdr + _QBLOCK4 - 1) // _QBLOCK4
        scales = np.frombuffer(buf, np.float32, count=n_blocks, offset=4)
        packed = np.frombuffer(buf, np.uint8, count=(n_hdr + 1) // 2,
                               offset=4 + 4 * n_blocks)
        out = np.asarray(_dec_u4(jnp.asarray(packed), jnp.asarray(scales),
                                 int(n_hdr)))
        if out.size != n:
            raise ValueError(f"decoded {out.size} elements, expected {n}")
        return out
    raise ValueError(f"unknown codec {codec}")


# -- whole-part encode for the all-reduce hot path -----------------------

class EncodedPart:
    """A u8- or u4-quantized all-reduce part: packed device buffers from
    one encode call, materialized to host AT MOST once (lock-guarded —
    chunk producers race on it from the send pool), then framed per chunk
    by byte slicing. ``decoded`` caches the device dequantize of the same
    buffers for the gather phase's local apply, so the applied values are
    exactly the wire bytes' values."""

    def __init__(self, codes: jax.Array, scales: jax.Array, n: int,
                 codec: int = compression.UNIFORM8BIT):
        self._codes_dev = codes          # u4: packed nibble pairs
        self._scales_dev = scales
        self.n = n
        self.codec = codec
        self._lock = threading.Lock()
        self._codes: Optional[np.ndarray] = None
        self._scales: Optional[np.ndarray] = None
        self._decoded: Optional[np.ndarray] = None

    def _materialize(self) -> None:
        with self._lock:
            if self._codes is None:
                self._codes, self._scales = jax.device_get(
                    (self._codes_dev, self._scales_dev))

    def decoded_dev(self) -> jax.Array:
        """The dequantized part as a DEVICE array — what every receiver
        of these wire bytes decodes; the error-feedback residual update
        (swarm/error_feedback.py) subtracts it from the compensated
        gradient without a host round-trip."""
        if self.codec == compression.UNIFORM4BIT:
            return _dec_u4(self._codes_dev, self._scales_dev, self.n)
        return _dec_u8(self._codes_dev, self._scales_dev)

    def _decode(self) -> np.ndarray:
        with self._lock:
            if self._decoded is None:
                self._decoded = np.asarray(self.decoded_dev())
            return self._decoded


def encode_part(src, lo: int, hi: int,
                codec: int = compression.UNIFORM8BIT) -> "EncodedPart":
    """Quantize ``src[lo:hi]`` blockwise (u8 or u4) in ONE device call
    (async dispatch — returns immediately with the device buffers in
    flight). ``src`` is the device-flattened gradient vector; a host
    array works too (pushed once, e.g. the gather phase's
    host-accumulated part)."""
    piece = _as_flat_f32(src[lo:hi])
    if codec == compression.UNIFORM4BIT:
        packed, scales = _encode_u4(piece)
        return EncodedPart(packed, scales, hi - lo, codec)
    if codec != compression.UNIFORM8BIT:
        raise ValueError(f"encode_part: unsupported codec {codec}")
    codes, scales = _encode_u8(piece)
    return EncodedPart(codes, scales, hi - lo, codec)


def part_payload(enc: EncodedPart, clo: int, chi: int) -> bytes:
    """Wire payload of the chunk ``[clo, chi)`` of an encoded part —
    byte-identical to ``compression.compress(part[clo:chi], enc.codec)``
    provided ``clo`` is a multiple of the codec's quant block (the
    caller guarantees it: CHUNK_ELEMS is a multiple of both, and the u4
    block's evenness means nibble pairs never straddle a chunk). Pure
    byte slicing after the one-time materialize."""
    block = compression.codec_block(enc.codec)
    assert clo % block == 0, "chunk start must align to the quant block"
    enc._materialize()
    b_lo = clo // block
    b_hi = (chi + block - 1) // block
    if enc.codec == compression.UNIFORM4BIT:
        body = enc._codes[clo // 2:(chi + 1) // 2]
    else:
        body = enc._codes[clo:chi]
    return (struct.pack(">I", chi - clo)
            + enc._scales[b_lo:b_hi].tobytes()
            + body.tobytes())


def part_decode(enc: EncodedPart, clo: int, chi: int) -> np.ndarray:
    """The dequantized values of chunk ``[clo, chi)`` — the same lossy
    values every receiver of :func:`part_payload`'s bytes decodes, for
    the part owner's local apply. One device dequantize per part, then
    host views."""
    return enc._decode()[clo:chi]


# -- fused owner accumulation (the reduce phase's hot path) ---------------
# Per completed sender: wire codes + scales in, the f32 part accumulator
# in/out (DONATED) — the owner's per-chunk host f32 numpy (decode into a
# buffer, then acc += seg * w) collapses into device dispatches, and
# only the finished accumulator ever crosses back to the host (once, at
# averaging time). The decode·weight multiply and the accumulator add
# are deliberately TWO executables, not one: inside a single XLA program
# the CPU (and TPU) backends contract mul+add into an FMA — one rounding
# where the host path takes two — which flips low bits against the r14
# protocol and the audit replay (measured: optimization_barrier does NOT
# block the contraction). Across executable boundaries contraction is
# impossible, and nothing but the two dispatches' latency is lost.

@jax.jit
def _dec_mul_u8(codes: jax.Array, scales: jax.Array,
                w: jax.Array) -> jax.Array:
    return _dec_u8(codes, scales) * w


@functools.partial(jax.jit, static_argnums=3)
def _dec_mul_u4(packed: jax.Array, scales: jax.Array, w: jax.Array,
                n: int) -> jax.Array:
    return _dec_u4(packed, scales, n) * w


@functools.partial(jax.jit, donate_argnums=(0,))
def _acc_add(acc: jax.Array, contrib: jax.Array) -> jax.Array:
    return acc + contrib


def add_contrib(acc: jax.Array, contrib) -> jax.Array:
    """Add a HOST-computed weighted contribution to the donated device
    accumulator — the fused reduce's fallback for senders whose frames
    arrived in some other codec (an unpinned round's r14 mixed-codec
    interop). The add is the same IEEE f32 elementwise op as the host
    path's, so parity holds."""
    return _acc_add(acc, jnp.asarray(contrib))


def accumulator_init(src, lo: int, hi: int, weight: float) -> jax.Array:
    """The owner's own contribution as the device accumulator seed —
    ``src[lo:hi] * weight`` with the same f32 multiply the host path
    runs."""
    return _as_flat_f32(src[lo:hi]) * jnp.float32(weight)


def fused_accumulate(acc: jax.Array, payloads: Sequence[bytes],
                     codec: int, n: int, w: float) -> jax.Array:
    """Apply one sender's complete contribution to the donated device
    accumulator. ``payloads`` are the sender's validated wire chunk
    payloads in chunk order (compression.quant_payload_valid): their
    scale and code byte ranges concatenate into the whole part's
    because chunk boundaries are quant-block multiples."""
    block = compression.codec_block(codec)
    # one header parse per payload (this IS the reduce hot path)
    ns = [struct.unpack(">I", p[:4])[0] for p in payloads]
    blks = [(pn + block - 1) // block for pn in ns]
    scales = np.concatenate([
        np.frombuffer(p, np.float32, count=nb, offset=4)
        for p, nb in zip(payloads, blks)])
    if codec == compression.UNIFORM4BIT:
        codes = np.concatenate([
            np.frombuffer(p, np.uint8, count=(pn + 1) // 2,
                          offset=4 + 4 * nb)
            for p, pn, nb in zip(payloads, ns, blks)])
        contrib = _dec_mul_u4(jnp.asarray(codes), jnp.asarray(scales),
                              jnp.float32(w), n)
        return _acc_add(acc, contrib)
    codes = np.concatenate([
        np.frombuffer(p, np.uint8, count=pn, offset=4 + 4 * nb)
        for p, pn, nb in zip(payloads, ns, blks)])
    contrib = _dec_mul_u8(jnp.asarray(codes), jnp.asarray(scales),
                          jnp.float32(w))
    return _acc_add(acc, contrib)
