"""Blockwise int8 quantization — the ZeRO++ / compression workhorse.

TPU-native equivalent of reference ``csrc/quantization/`` (``quantize.cu``
symmetric block quant, ``swizzled_quantize.cu`` comm-layout variant,
``quant_reduce.cu`` fused dequant+reduce for qgZ): values are grouped into
fixed-size blocks, each block scaled by absmax/127 to int8.

Used by: qwZ (quantized weight allgather), qgZ (quantized gradient
all-to-all reduce), weight-only inference quantization, 1-bit optimizer wire
format. Pallas kernel for TPU; jnp fallback elsewhere (identical numerics).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .registry import registry, use_pallas


def _quant_kernel(x_ref, v_ref, s_ref):
    x = x_ref[:].astype(jnp.float32)  # [rows, block]
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    v_ref[:] = q.astype(jnp.int8)
    s_ref[:] = scale


def _pad_to_blocks(flat, block_size):
    pad = (-flat.shape[0]) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def quantize_int8_blockwise(x, block_size: int = 2048,
                            force_pallas: Optional[bool] = None,
                            interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-block int8 quant. Returns (values int8 [N], scales
    fp32 [N/block]); padding (zeros) is included in the trailing block."""
    orig = x.shape
    flat, _ = _pad_to_blocks(x.reshape(-1), block_size)
    rows = flat.shape[0] // block_size
    blocks = flat.reshape(rows, block_size)
    if use_pallas(force_pallas) or interpret:
        tile = min(rows, 256)
        pad_r = (-rows) % tile
        if pad_r:
            blocks = jnp.pad(blocks, ((0, pad_r), (0, 0)))
        v, s = pl.pallas_call(
            _quant_kernel,
            grid=(blocks.shape[0] // tile, ),
            in_specs=[pl.BlockSpec((tile, block_size), lambda i: (i, 0))],
            out_specs=[
                pl.BlockSpec((tile, block_size), lambda i: (i, 0)),
                pl.BlockSpec((tile, 1), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(blocks.shape, jnp.int8),
                jax.ShapeDtypeStruct((blocks.shape[0], 1), jnp.float32),
            ],
            interpret=interpret,
        )(blocks)
        if pad_r:
            v, s = v[:rows], s[:rows]
    else:
        xf = blocks.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
        s = jnp.where(absmax == 0, 1.0, absmax / 127.0)
        v = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return v.reshape(-1), s.reshape(-1)


def dequantize_int8_blockwise(values, scales, shape, block_size: int = 2048,
                              dtype=jnp.float32):
    """Inverse of quantize_int8_blockwise (reference dequantize.cu)."""
    rows = values.shape[0] // block_size
    x = values.reshape(rows, block_size).astype(jnp.float32) * scales.reshape(rows, 1)
    n = 1
    for d in shape:
        n *= d
    return x.reshape(-1)[:n].reshape(shape).astype(dtype)


registry.register("quantizer_int8", "pallas", True)


# ---------------------------------------------------------------- FP8/FP quant

def quantize_fp8(x, dtype=jnp.float8_e4m3fn, block_size: int = 2048):
    """Blockwise-scaled FP8 quantization.

    Reference ``csrc/fp_quantizer/fp_quantize.cu`` (FP6-LLM-style low-bit
    float formats for weights). TPU-native version targets the hardware's
    fp8 dtypes (e4m3 for weights/activations, e5m2 for gradients); blocks
    are scaled so the absmax maps to the format's max normal, preserving
    dynamic range the way the reference's per-group scales do. For the
    6-bit tier see ``quantize_fp6_blockwise`` below (bit-packed e3m2
    storage, dequantized in-graph).

    Returns (values: dtype, scales: f32 per block).
    """
    finfo_max = float(jnp.finfo(dtype).max)
    flat = x.reshape(-1)
    padded, _ = _pad_to_blocks(flat, block_size)
    blocks = padded.reshape(-1, block_size).astype(jnp.float32)
    scales = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / finfo_max
    scales = jnp.maximum(scales, 1e-12)
    values = (blocks / scales).astype(dtype)
    return values, scales[:, 0]


def dequantize_fp8(values, scales, shape, block_size: int = 2048):
    """Inverse of quantize_fp8."""
    blocks = values.astype(jnp.float32) * scales[:, None]
    import numpy as _np
    n = int(_np.prod(shape))
    return blocks.reshape(-1)[:n].reshape(shape)


registry.register("fp_quantizer", "xla", True,
                  "fp8 e4m3/e5m2 native dtypes + fp6 e3m2 packed storage")


# ------------------------------------------------------- int4 (WoQ) packing

def quantize_int4_blockwise(x, block_size: int = 2048):
    """Weight-only INT4: symmetric per-block quant to [-7, 7], two nibbles
    packed per int8 byte (reference ``inference/quantization`` WoQ int4 and
    ``quantize_intX.cu``). Returns (packed int8 [N/2], scales f32)."""
    flat = x.reshape(-1)
    padded, _ = _pad_to_blocks(flat, block_size)
    blocks = padded.reshape(-1, block_size).astype(jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True), 1e-12) / 7.0
    q = jnp.clip(jnp.round(blocks / scales), -7, 7).astype(jnp.int8)  # [-7,7]
    q = q.reshape(-1)
    # pack: low nibble = even idx, high nibble = odd idx (offset-8 unsigned)
    u = (q + 8).astype(jnp.uint8)
    packed = (u[0::2] | (u[1::2] << 4)).astype(jnp.int8)
    return packed, scales[:, 0]


def dequantize_int4_blockwise(packed, scales, shape, block_size: int = 2048):
    """Inverse of quantize_int4_blockwise."""
    import numpy as _np
    u = packed.astype(jnp.uint8)
    lo = (u & 0xF).astype(jnp.int32) - 8
    hi = (u >> 4).astype(jnp.int32) - 8
    q = jnp.stack([lo, hi], axis=1).reshape(-1).astype(jnp.float32)
    blocks = q.reshape(-1, block_size) * scales[:, None]
    n = int(_np.prod(shape))
    return blocks.reshape(-1)[:n].reshape(shape)


registry.register("quantizer_int4", "xla", True, "weight-only int4, nibble-packed")


# ------------------------------------------------------- FP6 (e3m2) packing

# FP6-LLM's weight format (reference ``csrc/fp_quantizer/fp_quantize.cu`` +
# ``ops/fp_quantizer/quantize.py:43``): sign(1) exp(3) mantissa(2), bias 3,
# no inf/nan. Magnitude codes 0..31: m<4 are subnormals (m * 2^-4), else
# (1 + (m&3)/4) * 2^((m>>2) - 3). Max normal = 1.75 * 2^4 = 28.
_FP6_MAX = 28.0


def _fp6_encode_mag(mag):
    """Magnitude (fp32, in [0, 28]) → 5-bit magnitude code, round-to-nearest.
    The carry trick: code = E*4 + round((mag/2^(E-3) - 1)*4) rolls a mantissa
    overflow into the next exponent automatically."""
    mag = jnp.minimum(mag, _FP6_MAX)
    safe = jnp.maximum(mag, 1e-30)
    E = jnp.clip(jnp.floor(jnp.log2(safe)) + 3, 1, 7)
    man = jnp.round((mag / jnp.exp2(E - 3) - 1.0) * 4.0)
    normal_code = E * 4 + man
    sub_code = jnp.round(mag * 16.0)  # units of 2^-4; 4 rolls into E=1,M=0
    code = jnp.where(mag < 0.25, sub_code, normal_code)
    return jnp.clip(code, 0, 31).astype(jnp.uint8)


def _fp6_decode_mag(code):
    E = (code >> 2).astype(jnp.float32)
    man = (code & 0x3).astype(jnp.float32)
    sub = code.astype(jnp.float32) / 16.0
    return jnp.where(code < 4, sub, (1.0 + man / 4.0) * jnp.exp2(E - 3.0))


def quantize_fp6_blockwise(x, block_size: int = 2048):
    """Weight-only FP6 (e3m2): per-block scale maps absmax → 28, codes are
    bit-packed 4-per-3-bytes (true 6-bit storage — the quality-per-bit point
    between int4 and int8 that FP6-LLM ships). Returns
    (packed uint8 [3N/4], scales f32 [N/block])."""
    if block_size % 4:
        raise ValueError(f"block_size must be a multiple of 4, got {block_size}")
    flat = x.reshape(-1)
    padded, _ = _pad_to_blocks(flat, block_size)
    blocks = padded.reshape(-1, block_size).astype(jnp.float32)
    scales = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True),
                         1e-12) / _FP6_MAX
    scaled = blocks / scales
    codes = _fp6_encode_mag(jnp.abs(scaled))
    codes = codes | (jnp.signbit(scaled).astype(jnp.uint8) << 5)
    c = codes.reshape(-1, 4).astype(jnp.uint32)
    c0, c1, c2, c3 = c[:, 0], c[:, 1], c[:, 2], c[:, 3]
    b0 = c0 | ((c1 & 0x3) << 6)
    b1 = (c1 >> 2) | ((c2 & 0xF) << 4)
    b2 = (c2 >> 4) | (c3 << 2)
    packed = jnp.stack([b0, b1, b2], axis=1).reshape(-1).astype(jnp.uint8)
    return packed, scales[:, 0]


def dequantize_fp6_blockwise(packed, scales, shape, block_size: int = 2048,
                             dtype=jnp.float32):
    """Inverse of quantize_fp6_blockwise — shift/mask unpack + exp2 decode,
    all elementwise (XLA fuses it into the consuming matmul's operand read)."""
    import numpy as _np
    b = packed.reshape(-1, 3).astype(jnp.uint32)
    b0, b1, b2 = b[:, 0], b[:, 1], b[:, 2]
    c0 = b0 & 0x3F
    c1 = (b0 >> 6) | ((b1 & 0xF) << 2)
    c2 = (b1 >> 4) | ((b2 & 0x3) << 4)
    c3 = b2 >> 2
    codes = jnp.stack([c0, c1, c2, c3], axis=1).reshape(-1).astype(jnp.uint8)
    mag = _fp6_decode_mag(codes & 0x1F)
    vals = jnp.where(codes >> 5, -mag, mag)
    blocks = vals.reshape(-1, block_size) * scales[:, None]
    n = int(_np.prod(shape))
    return blocks.reshape(-1)[:n].reshape(shape).astype(dtype)


registry.register("quantizer_fp6", "xla", True,
                  "weight-only fp6 e3m2, 4-codes-per-3-bytes packed")
