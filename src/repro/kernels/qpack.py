"""Pallas TPU kernels for the qpack block compression engine.

The compression engine is the paper's per-device hot path (Fig. 3 steps 2-3).
On TPU we replace the LZ77 sequential matcher with rate-adaptive quantization
(DESIGN.md §3): a VPU-friendly reduction (block amax) + elementwise quantize +
bit pack. Tiling: ``TILE`` blocks per grid step, one block per VMEM row.

Two kernel families:

  * ``qpack_encode_2d``/``qpack_decode_2d`` — fixed-rate quantize+pack (the
    KV-cache / optimizer-state fast path), one independently-scaled block
    per row.
  * ``qpack_fused_encode_2d``/``qpack_fused_decode_2d`` — the demotion /
    promotion engine: per-block rate selection (zero-detect + amax ->
    {zero, 4-bit, 8-bit, raw}, CRAM/BDI-style). The encode quantizes,
    measures both round-trip errors and packs every candidate in ONE grid
    pass; XLA then picks the rate with the oracle's divide and assembles
    the row (``_fused_rates``, ``_fused_dense``), giving the dense per-block
    byte layout of ``core.compressor._encode_block_dense`` bit-for-bit.
    The jnp compressor remains the bit-identity oracle
    (tests/test_qpack_fused.py).

**Word layout.** Mosaic has no lane compaction (``x[:, 0::2]``) and no
8-bit shifts, so the kernels compute in 32-bit lanes only. Packed data
crosses the kernel boundary as little-endian uint32 words (the wrappers
convert to and from the byte format with ``common.utils`` helpers). An
encode kernel reads its values as eight *planes* on a leading axis
(``to_planes``: plane k holds values ``8w + k``), so that word ``w`` of a
4-bit stream is an OR of the eight planes at lane ``w``, and writes its
words as *groups* on a leading axis (wider fields give several words per
plane lane; ``from_groups`` interleaves them in XLA). An encode kernel
thus never moves a value between lanes. Lane-sliced planes (plane k at
lanes ``[kV/8, (k+1)V/8)`` of one row) are refused: on a v5e (jax 0.9.0)
a 512-value kernel that packed 4-bit codes from such 64-lane slices and
wrote a second output lost plane 5 of every word (PERF.md, Findings).
Decoding interleaves unpacked 32-bit fields in-kernel
(``stack(...).reshape``), a layout Mosaic supports.

**No divides in Mosaic.** A Mosaic reciprocal need not round as XLA's
divide does, and one ulp moves a code across a rounding boundary. So the
per-row scales and their reciprocals are computed in XLA
(``core.bitpack.block_scale``, exactly as the jnp oracle computes them)
and passed in, the kernels only multiply, and rate selection's
``err / amax`` runs in XLA after the kernel.

``interpret=None`` auto-detects the backend: compiled kernels on TPU,
the Pallas interpreter elsewhere. Pass an explicit bool to override.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.utils import bytes_to_u32, f32_to_bytes, u32_to_bytes
from repro.core.bitpack import (RATE_4BIT, RATE_8BIT, RATE_RAW, RATE_ZERO,
                                block_scale)

TILE = 8     # blocks per grid step
PLANES = 8   # value planes per encode block: 4-bit fields per uint32 word


def _qmax(bits: int) -> float:
    return float(2 ** (bits - 1) - 1)


def resolve_interpret(interpret) -> bool:
    """None -> interpret only off-TPU (compiled kernels on real hardware)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def to_planes(x: jnp.ndarray) -> jnp.ndarray:
    """[N, V] -> [8, N, V/8]: plane k holds values ``8w + k`` of every
    block at lane ``w``, the layout the encode kernels read."""
    n, v = x.shape
    return x.reshape(n, v // PLANES, PLANES).transpose(2, 0, 1)


def from_groups(words: jnp.ndarray) -> jnp.ndarray:
    """[G, N, W] word groups -> [N, G*W] stream words: word ``G*w + s``
    is group ``s`` at lane ``w`` (inverse layout of ``_pack_planes``)."""
    g, n, w = words.shape
    return words.transpose(1, 2, 0).reshape(n, w * g)


def _interleave(parts):
    """[T, W] x m -> [T, m*W] with ``out[:, m*j + r] = parts[r][:, j]``."""
    t, w = parts[0].shape
    return jnp.stack(parts, axis=-1).reshape(t, w * len(parts))


def _pack_planes(planes, bits: int) -> list:
    """Eight plane fields [T, W] (int32/uint32, low ``bits`` used) ->
    ``bits/4`` uint32 word groups [T, W], lowest field first: group ``s``
    packs planes ``s*32/bits`` onwards (``from_groups`` orders them)."""
    per_word = 32 // bits
    mask = jnp.uint32((1 << bits) - 1)
    groups = []
    for s in range(PLANES // per_word):
        acc = jnp.zeros(planes[0].shape, jnp.uint32)
        for r in range(per_word):
            field = planes[s * per_word + r].astype(jnp.uint32) & mask
            acc = acc | (field << jnp.uint32(bits * r))
        groups.append(acc)
    return groups


def _store_groups(ref, groups) -> None:
    for s, g in enumerate(groups):
        ref[s] = g


def _unpack_words(words: jnp.ndarray, bits: int) -> jnp.ndarray:
    """uint32 words [T, W] -> uint32 fields [T, W*32/bits] in stream
    order, lowest field first."""
    mask = jnp.uint32((1 << bits) - 1)
    return _interleave([(words >> jnp.uint32(bits * r)) & mask
                        for r in range(32 // bits)])


def _sign_extend(u: jnp.ndarray, bits: int) -> jnp.ndarray:
    sign = 1 << (bits - 1)
    return (u.astype(jnp.int32) ^ sign) - sign


def _quantize(xf: jnp.ndarray, recip: jnp.ndarray, bits: int) -> jnp.ndarray:
    """core.bitpack.quantize_block's codes (int32) given its reciprocal
    scale; the divide that produced ``recip`` ran in XLA."""
    qmax = _qmax(bits)
    return jnp.clip(jnp.round(xf * recip), -qmax - 1, qmax).astype(jnp.int32)


def _encode_kernel(x_ref, recip_ref, words_ref, *, bits: int):
    recip = recip_ref[...]
    _store_groups(words_ref, _pack_planes(
        [_quantize(x_ref[k].astype(jnp.float32), recip, bits)
         for k in range(PLANES)], bits))


def _decode_kernel(codes_ref, scales_ref, o_ref, *, bits: int):
    q = _sign_extend(_unpack_words(codes_ref[...], bits), bits)
    o_ref[...] = (q.astype(jnp.float32) * scales_ref[...]).astype(o_ref.dtype)


def _amax(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)


def _planes_spec(w: int, lead: int = PLANES) -> pl.BlockSpec:
    """``TILE`` blocks of a leading-axis plane or word-group array."""
    return pl.BlockSpec((lead, TILE, w), lambda i: (0, i, 0))


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def qpack_encode_2d(x: jnp.ndarray, *, bits: int = 4,
                    interpret: bool | None = None):
    """Blocks x [N, B] -> (codes uint8[N, B*bits/8], scales f32[N]).

    N must be a multiple of TILE and B of 8."""
    interpret = resolve_interpret(interpret)
    n, b = x.shape
    assert n % TILE == 0 and b % PLANES == 0, (n, b)
    w, groups = b // PLANES, bits * PLANES // 32
    scales, recip = block_scale(_amax(x), bits)
    words = pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits),
        grid=(n // TILE,),
        in_specs=[_planes_spec(w), pl.BlockSpec((TILE, 1), lambda i: (i, 0))],
        out_specs=_planes_spec(w, groups),
        out_shape=jax.ShapeDtypeStruct((groups, n, w), jnp.uint32),
        interpret=interpret,
    )(to_planes(x), recip)
    return u32_to_bytes(from_groups(words)), scales[:, 0]


@functools.partial(jax.jit, static_argnames=("bits", "out_dtype",
                                             "interpret"))
def qpack_decode_2d(codes: jnp.ndarray, scales: jnp.ndarray, *, bits: int = 4,
                    out_dtype=jnp.bfloat16, interpret: bool | None = None):
    """(codes uint8[N, B*bits/8], scales f32[N]) -> x [N, B]."""
    interpret = resolve_interpret(interpret)
    n, bp = codes.shape
    b = bp * 8 // bits
    assert n % TILE == 0 and bp % 4 == 0, (n, bp)
    return pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits),
        grid=(n // TILE,),
        in_specs=[pl.BlockSpec((TILE, bp // 4), lambda i: (i, 0)),
                  pl.BlockSpec((TILE, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, b), out_dtype),
        interpret=interpret,
    )(bytes_to_u32(codes), scales.reshape(n, 1).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Fused demote / promote kernels (rate-adaptive engine, DESIGN.md §14).
# ---------------------------------------------------------------------------

def _fused_params(amax: jnp.ndarray) -> jnp.ndarray:
    """XLA side of the fused encode: per-row f32 [N, 4] = (s4, 1/s4, s8,
    1/s8), as core.bitpack.quantize_block computes them."""
    s4, r4 = block_scale(amax, 4)
    s8, r8 = block_scale(amax, 8)
    return jnp.concatenate([s4, r4, s8, r8], axis=1)


def _fused_encode_kernel(x_ref, p_ref, e4_ref, e8_ref, c4_ref, c8_ref,
                         raw_ref, *, lossless: bool):
    xs = [x_ref[k] for k in range(PLANES)]              # [TILE, V/8] each
    xf = [x.astype(jnp.float32) for x in xs]
    xb = [x.astype(jnp.bfloat16) for x in xs]
    p = p_ref[...]                                      # [TILE, 4]
    s4, r4, s8, r8 = (p[:, k:k + 1] for k in range(4))
    q4 = [_quantize(x, r4, 4) for x in xf]
    q8 = [_quantize(x, r8, 8) for x in xf]
    # the round-trip errors core.compressor.select_rate compares: against
    # the bf16 values when lossless (exact iff 0), else against x
    refs = [b.astype(jnp.float32) for b in xb] if lossless else xf

    def err(qs, s):
        worst = None
        for q, r in zip(qs, refs):
            deq = (q.astype(jnp.float32) * s).astype(jnp.bfloat16)
            d = jnp.abs(deq.astype(jnp.float32) - r)
            worst = d if worst is None else jnp.maximum(worst, d)
        return jnp.max(worst, axis=-1, keepdims=True)

    e4_ref[...] = err(q4, s4)
    e8_ref[...] = err(q8, s8)
    # the three candidate encodings as word groups; XLA picks one per row
    _store_groups(c4_ref, _pack_planes(q4, 4))
    _store_groups(c8_ref, _pack_planes(q8, 8))
    _store_groups(raw_ref, _pack_planes(
        [jax.lax.bitcast_convert_type(b.astype(jnp.float32), jnp.uint32)
         >> jnp.uint32(16) for b in xb], 16))


def _fused_rates(amax, e4, e8, *, tol4, tol8, lossless, zero_elision):
    """XLA side: core.compressor.select_rate's choice from the kernel's
    round-trip errors, with the oracle's own divide."""
    if lossless:
        ok4, ok8 = e4 == 0, e8 == 0
    else:
        safe = jnp.where(amax > 0, amax, 1.0)
        ok4 = e4 / safe <= tol4
        ok8 = e8 / safe <= tol8
    rate = jnp.where(ok8, RATE_8BIT, RATE_RAW)
    rate = jnp.where(ok4, RATE_4BIT, rate)
    rate = jnp.where(amax == 0, RATE_ZERO, rate).astype(jnp.int32)
    if not zero_elision:
        rate = jnp.maximum(rate, RATE_4BIT)
    return rate


def _fused_dense(raw, c4, c8, params, rates):
    """XLA side: the dense byte rows of core.compressor._encode_block_dense
    from the kernel's words — f32 scale bytes | packed codes | zero pad
    for the 4- and 8-bit rates, bf16 bytes for raw, zeros for zero."""
    n, nb = raw.shape[0], 4 * raw.shape[1]

    def coded(codes, scale):
        return jnp.concatenate(
            [f32_to_bytes(scale), u32_to_bytes(codes),
             jnp.zeros((n, nb - 4 - 4 * codes.shape[1]), jnp.uint8)], axis=1)

    dense = jnp.where(rates == RATE_RAW, u32_to_bytes(raw), jnp.uint8(0))
    dense = jnp.where(rates == RATE_4BIT, coded(c4, params[:, 0:1]), dense)
    return jnp.where(rates == RATE_8BIT, coded(c8, params[:, 2:3]), dense)


def _fused_decode_kernel(dense_ref, rates_ref, o_ref):
    d = dense_ref[...]                                  # [TILE, V/2] uint32
    rate = rates_ref[...]                               # [TILE, 1] int32
    t, nw = d.shape
    v = 2 * nw
    scale = jax.lax.bitcast_convert_type(d[:, 0:1], jnp.float32)
    # the codes after the scale word, moved back to lane 0: Mosaic cannot
    # interleave a value that starts at lane offset 1
    codes = jnp.concatenate([d[:, 1:], d[:, :1]], axis=1)
    # 4-bit: sign-extended nibbles (core.bitpack.unpack4)
    q4 = _sign_extend(_unpack_words(codes[:, :v // 8], 4), 4)
    out4 = (q4.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    # 8-bit: bit-identity int8 (core.bitpack.unpack8)
    q8 = _sign_extend(_unpack_words(codes[:, :v // 4], 8), 8)
    out8 = (q8.astype(jnp.float32) * scale).astype(jnp.bfloat16)
    # raw: little-endian bf16 (core.bitpack.bytes_to_raw)
    raw = jax.lax.bitcast_convert_type(
        _unpack_words(d, 16) << jnp.uint32(16), jnp.float32
    ).astype(jnp.bfloat16)

    out = jnp.where(rate == RATE_4BIT, out4,
                    jnp.zeros((t, v), jnp.bfloat16))
    out = jnp.where(rate == RATE_8BIT, out8, out)
    out = jnp.where(rate == RATE_RAW, raw, out)
    o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("tol4", "tol8", "lossless",
                                             "zero_elision", "quanta",
                                             "interpret"))
def qpack_fused_encode_2d(x: jnp.ndarray, *, tol4: float = 0.10,
                          tol8: float = 0.01, lossless: bool = False,
                          zero_elision: bool = True,
                          quanta: tuple = (0, 3, 5, 8),
                          interpret: bool | None = None):
    """Fused demote kernel: blocks x [N, V] (bf16/f32 values) ->
    (dense uint8[N, 2V], rates int32[N], quanta int32[N]). One grid pass
    quantizes every block at both rates, measures the round-trip errors
    and packs all three candidates; XLA picks the rate and the row.

    ``dense`` rows are byte-identical to ``_encode_block_dense``; ``quanta``
    is the static per-rate size table (core.compressor.block_quanta_table).
    N must be a multiple of TILE; V a multiple of 8."""
    interpret = resolve_interpret(interpret)
    n, v = x.shape
    assert n % TILE == 0 and v % PLANES == 0, (n, v)
    amax = _amax(x)
    params = _fused_params(amax)
    w = v // PLANES
    row = lambda k: pl.BlockSpec((TILE, k), lambda i: (i, 0))
    groups = (1, 2, 4)                                   # c4, c8, raw
    e4, e8, *cands = pl.pallas_call(
        functools.partial(_fused_encode_kernel, lossless=lossless),
        grid=(n // TILE,),
        in_specs=[_planes_spec(w), row(4)],
        out_specs=[row(1), row(1)] + [_planes_spec(w, g) for g in groups],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32)] * 2 +
                  [jax.ShapeDtypeStruct((g, n, w), jnp.uint32)
                   for g in groups],
        interpret=interpret,
    )(to_planes(x), params)
    c4, c8, raw = (from_groups(c) for c in cands)
    rates = _fused_rates(amax, e4, e8, tol4=tol4, tol8=tol8,
                         lossless=lossless, zero_elision=zero_elision)
    qnt = jnp.asarray(quanta, jnp.int32)[rates[:, 0]]
    return _fused_dense(raw, c4, c8, params, rates), rates[:, 0], qnt


@functools.partial(jax.jit, static_argnames=("interpret",))
def qpack_fused_decode_2d(dense: jnp.ndarray, rates: jnp.ndarray, *,
                          interpret: bool | None = None):
    """Fused promote kernel: (dense uint8[N, 2V], rates int32[N]) ->
    bf16 [N, V] (unpack + dequant for all four rates in one pass)."""
    interpret = resolve_interpret(interpret)
    n, nb = dense.shape
    v = nb // 2
    assert n % TILE == 0 and v % PLANES == 0, (n, v)
    return pl.pallas_call(
        _fused_decode_kernel,
        grid=(n // TILE,),
        in_specs=[pl.BlockSpec((TILE, v // 2), lambda i: (i, 0)),
                  pl.BlockSpec((TILE, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((TILE, v), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, v), jnp.bfloat16),
        interpret=interpret,
    )(bytes_to_u32(dense), rates.reshape(n, 1).astype(jnp.int32))
