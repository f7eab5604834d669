"""Vectorized bit packing/unpacking for the rate-adaptive block compressor.

TPU adaptation note (DESIGN.md §3): the paper's LZ-family block compressors are
sequential symbol matchers with per-byte control flow — no VPU/MXU analogue.
The management layer only requires *variable-size chunked output*; we produce it
with SIMD-friendly rate-adaptive quantization. These helpers are the pure-jnp
packing primitives shared by the jnp compressor and the Pallas kernels' oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.utils import (bitcast_bf16_to_u16, bitcast_u16_to_bf16,
                                bytes_to_u16, u16_to_bytes)

# Rate codes (block_type in metadata, 2 bits — §4.6 co-location format):
RATE_ZERO = 0          # all-zero block: no chunks (paper's zero page type)
RATE_4BIT = 1          # 4-bit quantized + per-block scale
RATE_8BIT = 2          # 8-bit quantized + per-block scale
RATE_RAW = 3           # incompressible: raw bf16 payload


def pack4(q: jnp.ndarray) -> jnp.ndarray:
    """int8[N] in [-8,7] -> uint8[N/2]; pairs packed little-nibble-first."""
    u = (q.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    lo = u[..., 0::2]
    hi = u[..., 1::2]
    return lo | (hi << jnp.uint8(4))


def unpack4(b: jnp.ndarray, n: int) -> jnp.ndarray:
    """uint8[N/2] -> int8[N] sign-extended from 4-bit."""
    lo = (b & jnp.uint8(0xF)).astype(jnp.int8)
    hi = (b >> jnp.uint8(4)).astype(jnp.int8)
    q = jnp.stack([lo, hi], axis=-1).reshape(b.shape[:-1] + (n,))
    # sign-extend 4-bit
    return jnp.where(q >= 8, q - 16, q)


def pack8(q: jnp.ndarray) -> jnp.ndarray:
    """int8[N] -> uint8[N] (bit identity)."""
    return q.astype(jnp.int8).view(jnp.uint8) if hasattr(q, "view") else \
        jax.lax.bitcast_convert_type(q.astype(jnp.int8), jnp.uint8)


def unpack8(b: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(b.astype(jnp.uint8), jnp.int8)


def block_scale(amax: jnp.ndarray, bits: int
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(scale, 1/scale) of blocks with max magnitude ``amax``. The one
    divide of the codec: the Pallas kernels receive both from XLA instead
    of dividing in Mosaic, whose reciprocal need not round as XLA's does."""
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.where(amax > 0, amax * jnp.float32(1.0 / qmax), 1.0)
    return scale, jnp.float32(1.0) / scale


def quantize_block(x: jnp.ndarray, bits: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-block quantization. Returns (codes int8, scale f32).

    Multiplies by the reciprocal scale (never divides per value), so the
    Pallas kernels, given ``block_scale`` from XLA, are bit-identical."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale, recip = block_scale(amax, bits)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * recip), -qmax - 1, qmax)
    return q.astype(jnp.int8), scale[..., 0]


def dequantize_block(q: jnp.ndarray, scale: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def raw_to_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """bf16[N] -> uint8[2N]."""
    return u16_to_bytes(bitcast_bf16_to_u16(x))


def bytes_to_raw(b: jnp.ndarray) -> jnp.ndarray:
    """uint8[2N] -> bf16[N]."""
    return bitcast_u16_to_bf16(bytes_to_u16(b))
