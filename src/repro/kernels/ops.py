"""Public wrappers around the Pallas kernels.

On a TPU the compiled Mosaic kernels run; elsewhere the Pallas interpreter
runs the same kernel bodies (``qpack.resolve_interpret``, decided at call
time). The wrappers adapt shapes to and from the flat layouts used
elsewhere (core.compressor.quantize_blocks et al.): every block of a
``[..., N]`` tensor becomes one kernel row, padded to whole ``TILE``s.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.kernels import flash_attn as _fa
from repro.kernels import kvc_attn as _ka
from repro.kernels import qpack as _qp


def _pad_rows(a: jnp.ndarray, fill=0) -> jnp.ndarray:
    """Pad the leading (block) axis to a whole number of kernel tiles."""
    pad = (-a.shape[0]) % _qp.TILE
    if not pad:
        return a
    return jnp.concatenate(
        [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)], axis=0)


def qpack_encode(x: jnp.ndarray, bits: int = 4, block: int = 512,
                 interpret: bool | None = None):
    """x[..., N] -> (codes uint8[..., N*bits/8], scales f32[..., N/block]).
    Shape-compatible with core.compressor.quantize_blocks."""
    lead = x.shape[:-1]
    n = x.shape[-1]
    nblk = n // block
    total = math.prod(lead) * nblk
    codes, scales = _qp.qpack_encode_2d(
        _pad_rows(x.reshape(total, block)), bits=bits, interpret=interpret)
    codes = codes[:total].reshape(lead + (n * bits // 8,))
    return codes, scales[:total].reshape(lead + (nblk,))


def qpack_decode(codes: jnp.ndarray, scales: jnp.ndarray, bits: int = 4,
                 block: int = 512, dtype=jnp.bfloat16,
                 interpret: bool | None = None) -> jnp.ndarray:
    lead = scales.shape[:-1]
    nblk = scales.shape[-1]
    total = math.prod(lead) * nblk
    x = _qp.qpack_decode_2d(
        _pad_rows(codes.reshape(total, block * bits // 8)),
        _pad_rows(scales.reshape(total), 1.0), bits=bits, out_dtype=dtype,
        interpret=interpret)
    return x[:total].reshape(lead + (nblk * block,))


def qpack_fused_encode(x: jnp.ndarray, *, tol4: float = 0.10,
                       tol8: float = 0.01, lossless: bool = False,
                       zero_elision: bool = True,
                       quanta: tuple = (0, 3, 5, 8),
                       interpret: bool | None = None):
    """Fused demote over blocks x [T, V]: pads T to the kernel tile and
    returns (dense uint8[T, 2V], rates int32[T], quanta int32[T])."""
    t = x.shape[0]
    dense, rates, qnt = _qp.qpack_fused_encode_2d(
        _pad_rows(x), tol4=tol4, tol8=tol8, lossless=lossless,
        zero_elision=zero_elision, quanta=tuple(quanta),
        interpret=interpret)
    return dense[:t], rates[:t], qnt[:t]


def qpack_fused_decode(dense: jnp.ndarray, rates: jnp.ndarray, *,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Fused promote over dense blocks [T, 2V] + rates [T] -> bf16 [T, V]."""
    out = _qp.qpack_fused_decode_2d(_pad_rows(dense), _pad_rows(rates),
                                    interpret=interpret)
    return out[:dense.shape[0]]


def kvc_decode_attention(q, k_codes, k_scales, v_codes, v_scales, lengths, *,
                         bits: int = 4, sm_scale: float | None = None,
                         t_blk: int = 128) -> jnp.ndarray:
    return _ka.kvc_decode_attention(
        q, k_codes, k_scales, v_codes, v_scales, lengths, bits=bits,
        sm_scale=sm_scale, t_blk=t_blk,
        interpret=_qp.resolve_interpret(None))


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None, tq: int = 128,
                    tk: int = 128) -> jnp.ndarray:
    return _fa.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               tq=tq, tk=tk,
                               interpret=_qp.resolve_interpret(None))
