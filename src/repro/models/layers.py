"""Core transformer layers: RMSNorm, RoPE, GQA/MLA attention, SwiGLU MLP.

Parameters are plain dict pytrees; every init returns (params, logical_axes)
mirrored trees so the launcher can derive NamedShardings from the rule table
in common/sharding.py (MaxText-style logical axes).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import MLAConfig, ModelConfig, YaRNConfig

Params = Dict[str, Any]
Axes = Dict[str, Any]


def _init(key, shape, scale=None, dtype=jnp.float32):
    if scale is None:
        scale = 1.0 / (shape[0] ** 0.5)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm with f32 *reductions* but bf16 *products*.

    Casting the whole input to f32 first (the naive form) makes every
    backward cotangent upstream of the cast f32 — including the TP dgrad
    partial-sums, which then all-reduce at 2x the bytes (measured 2x3.76GB
    f32 all-reduces per layer on arctic train, §Perf cell C-i3). Keeping the
    [B,S,d]-sized math in the input dtype halves that wire traffic; only the
    [B,S,1] variance runs in f32."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * w.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature factor 0.1 * mscale * ln(scale) + 1."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(dim: int, theta: float, y: YaRNConfig) -> np.ndarray:
    """YaRN inverse frequencies [dim/2]: the plain ones (extrapolation) for
    the fast dims, the plain ones / factor (interpolation) for the slow
    ones, and a linear ramp between the dims where a rotation over the
    original context turns ``beta_fast`` and ``beta_slow`` times."""
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** i
    inter = extra / y.factor

    def corr(rot):
        return dim * math.log(y.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               freqs=None) -> jnp.ndarray:
    """x [..., S, H, D] (D even), positions [..., S] -> rotated x; ``freqs``
    [D/2] overrides the plain inverse frequencies."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d, theta)                   # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Memory-efficient exact attention (training path; differentiable).
# ---------------------------------------------------------------------------

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, sm_scale: Optional[float] = None,
                      chunk: int = 1024) -> jnp.ndarray:
    """Flash-style online-softmax attention as a lax.scan over KV chunks —
    exact, differentiable, O(S·chunk) live memory (body is rematerialized).

    q [B,Sq,Hq,D]; k,v [B,Sk,Hkv,D]."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]                 # MLA: value dim may differ from qk dim
    g = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    chunk = min(chunk, Sk)
    assert Sk % chunk == 0
    nchunks = Sk // chunk
    qf = (q.astype(jnp.float32) * sm_scale).transpose(0, 2, 1, 3)  # [B,Hq,Sq,D]
    kc = k.reshape(B, nchunks, chunk, Hkv, D)
    vc = v.reshape(B, nchunks, chunk, Hkv, Dv)
    rows = jnp.arange(Sq)

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        kj = jnp.repeat(kj.astype(jnp.float32), g, axis=2)     # [B,chunk,Hq,D]
        vj = jnp.repeat(vj.astype(jnp.float32), g, axis=2)
        s = jnp.einsum("bhqd,bkhd->bhqk", qf, kj)
        if causal:
            cols = j * chunk + jnp.arange(chunk)
            mask = cols[None, :] <= (rows + (Sk - Sq))[:, None]
            s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vj)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hq, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hq, Sq, Dv), jnp.float32)
    # note: k chunk axis moved to front for scan
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(nchunks), kc.transpose(1, 0, 2, 3, 4),
         vc.transpose(1, 0, 2, 3, 4)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def gqa_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    params = {
        "wq": _init(ks[0], (d, hq * hd)),
        "wk": _init(ks[1], (d, hkv * hd)),
        "wv": _init(ks[2], (d, hkv * hd)),
        "wo": _init(ks[3], (hq * hd, d), scale=1.0 / ((hq * hd) ** 0.5)),
    }
    axes = {"wq": ("fsdp", "heads"), "wk": ("fsdp", "heads"),
            "wv": ("fsdp", "heads"), "wo": ("heads", "fsdp")}
    return params, axes


def gqa_apply_train(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    B, S, d = x.shape
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, hq, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, hkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, hkv, hd)
    pos = jnp.arange(S)[None, :]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=True)
    return o.reshape(B, S, hq * hd) @ p["wo"].astype(x.dtype)


def gqa_project_kv(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                   cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """K/V for new tokens (cache append). x [B,T,d] -> k,v [B,T,Hkv,D]."""
    B, T, _ = x.shape
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, T, hkv, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, T, hkv, hd)
    k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def gqa_project_q(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                  cfg: ModelConfig) -> jnp.ndarray:
    B, T, _ = x.shape
    hq, hd = cfg.num_heads, cfg.resolved_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, T, hq, hd)
    return apply_rope(q, positions, cfg.rope_theta)


def gqa_output(p: Params, o: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    B = o.shape[0]
    return o.reshape(B, -1, cfg.num_heads * cfg.resolved_head_dim) @ \
        p["wo"].astype(o.dtype)


# ---------------------------------------------------------------------------
# MLA attention (MiniCPM3 / DeepSeek-V2 style latent KV)
# ---------------------------------------------------------------------------

def mla_init(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    d, h = cfg.d_model, cfg.num_heads
    m = cfg.mla or MLAConfig()
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    params = {
        "wkv_a": _init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "wkv_b": _init(ks[3], (m.kv_lora_rank,
                               h * (m.qk_nope_head_dim + m.v_head_dim))),
        "wo": _init(ks[4], (h * m.v_head_dim, d),
                    scale=1.0 / ((h * m.v_head_dim) ** 0.5)),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
    }
    axes = {"wkv_a": ("fsdp", "latent"), "wkv_b": ("latent", "heads"),
            "wo": ("heads", "fsdp"), "kv_norm": ("latent",)}
    if m.q_lora_rank:
        params.update(wq_a=_init(ks[0], (d, m.q_lora_rank)),
                      wq_b=_init(ks[1], (m.q_lora_rank, h * qk)),
                      q_norm=jnp.ones((m.q_lora_rank,), jnp.float32))
        axes.update(wq_a=("fsdp", "latent"), wq_b=("latent", "heads"),
                    q_norm=("latent",))
    else:
        params["wq"] = _init(ks[0], (d, h * qk))
        axes["wq"] = ("fsdp", "heads")
    return params, axes


def mla_rope(x: jnp.ndarray, positions: jnp.ndarray,
             cfg: ModelConfig) -> jnp.ndarray:
    """RoPE of MLA's rope dims, YaRN-scaled where the config says so.
    Rotate-half over the dims as they are laid out; the published model
    rotates interleaved pairs, which with weights drawn here is a fixed
    permutation of the projection's columns."""
    y = (cfg.mla or MLAConfig()).yarn
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    out = apply_rope(x, positions, cfg.rope_theta,
                     yarn_freqs(x.shape[-1], cfg.rope_theta, y))
    mag = yarn_mscale(y.factor, y.mscale) / \
        yarn_mscale(y.factor, y.mscale_all_dim)
    return out if mag == 1.0 else (out * mag).astype(x.dtype)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk head dim), times mscale(factor, mscale_all_dim)^2 under
    YaRN."""
    m = cfg.mla or MLAConfig()
    sm = 1.0 / ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5)
    if m.yarn is not None and m.yarn.mscale_all_dim:
        sm *= yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2
    return sm


def mla_query(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
              cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Queries of new tokens x [B,T,d]: (q_nope [B,T,H,nope], q_rope
    [B,T,H,rope] rotated), through the q-LoRA or one full projection."""
    m = cfg.mla or MLAConfig()
    B, T, _ = x.shape
    if m.q_lora_rank:
        q = rms_norm(x @ p["wq_a"].astype(x.dtype), p["q_norm"], cfg.norm_eps)
        q = q @ p["wq_b"].astype(x.dtype)
    else:
        q = x @ p["wq"].astype(x.dtype)
    q = q.reshape(B, T, cfg.num_heads,
                  m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    return q_nope, mla_rope(q_rope, positions, cfg)


def mla_latent(p: Params, x: jnp.ndarray, positions: jnp.ndarray,
               cfg: ModelConfig) -> jnp.ndarray:
    """Latent KV for new tokens: [B,T, kv_lora_rank + rope_dim] — this *is*
    the cached quantity (a learned KV compression; IBEX then block-compresses
    the latent cache — the two compose, DESIGN.md §4)."""
    m = cfg.mla or MLAConfig()
    ckv = x @ p["wkv_a"].astype(x.dtype)
    c, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = mla_rope(k_rope[:, :, None, :], positions, cfg)[:, :, 0]
    return jnp.concatenate([c, k_rope], axis=-1)


def mla_attend(p: Params, x: jnp.ndarray, latent: jnp.ndarray,
               positions: jnp.ndarray, cfg: ModelConfig, *,
               causal: bool) -> jnp.ndarray:
    """Attention of x's queries over the latent cache (expanded per head)."""
    m = cfg.mla or MLAConfig()
    h = cfg.num_heads
    B, T, _ = x.shape
    S = latent.shape[1]
    q_nope, q_rope = mla_query(p, x, positions, cfg)
    c, k_rope = jnp.split(latent, [m.kv_lora_rank], axis=-1)
    kv = (c @ p["wkv_b"].astype(x.dtype)).reshape(
        B, S, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, h, m.qk_rope_head_dim))], axis=-1)
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = chunked_attention(qq, k, v, causal=causal,
                          sm_scale=mla_softmax_scale(cfg))
    return o.reshape(B, T, h * m.v_head_dim) @ p["wo"].astype(x.dtype)


def mla_apply_train(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    B, S, _ = x.shape
    pos = jnp.arange(S)[None, :]
    latent = mla_latent(p, x, pos, cfg)
    return mla_attend(p, x, latent, pos, cfg, causal=True)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, f: int) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 3)
    params = {"wi": _init(ks[0], (d, f)), "wg": _init(ks[1], (d, f)),
              "wo": _init(ks[2], (f, d), scale=1.0 / (f ** 0.5))}
    axes = {"wi": ("fsdp", "mlp"), "wg": ("fsdp", "mlp"), "wo": ("mlp", "fsdp")}
    return params, axes


def mlp_apply(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ p["wg"].astype(x.dtype)) * (x @ p["wi"].astype(x.dtype))
    return h @ p["wo"].astype(x.dtype)
