"""Serving decode path: IBEX-compressed paged KV cache + one-token step.

The KV cache *is* an IBEX pool specialized for append-only data:

  * hot window (promoted region) — last ``W`` tokens per sequence, bf16 ring
    buffer. New K/V lands here (first-touch data is stored hot, §4.1).
  * compressed region — every token older than ``W``, block-quantized
    (one block per (token, kv-head) over the head dim; 4 or 8 bits + f32
    scale). A token is compressed exactly once, when it ages out of the ring
    (its slot is reused) — the streaming analogue of clock demotion for
    append-only data, where *every* demotion is clean (§4.5: no recompression
    ever happens; the paper measures 62% clean on general traffic, KV reaches
    100%).

Two read paths for the compressed prefix:
  * fused  — dequantize-inside-attention in chunked jnp (the Pallas kvc
    kernel, ``kernels/kvc_attn.py``, is not on this path): HBM bytes =
    compressed bytes where XLA fuses the dequantization into the dot
    operands; at MLA's widths on a v5e it writes each dequantized chunk
    out first.                                                  [beyond-paper]
  * paper  — promote-then-read: the prefix is materialized to bf16 (an
    optimization_barrier'd buffer = the promoted-region write+read), then
    attended uncompressed.                                       [faithful]
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import MLAConfig, ModelConfig, ServeConfig, SSMConfig
from repro.core.compressor import dequantize_blocks, quantize_blocks
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.obs import spans

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Online-softmax partials and merging
# ---------------------------------------------------------------------------

class Partial(NamedTuple):
    m: jnp.ndarray     # [B, H, 1]
    l: jnp.ndarray     # [B, H, 1]
    acc: jnp.ndarray   # [B, H, D]


def merge_partials(a: Partial, b: Partial) -> Partial:
    m = jnp.maximum(a.m, b.m)
    ea, eb = jnp.exp(a.m - m), jnp.exp(b.m - m)
    return Partial(m, a.l * ea + b.l * eb, a.acc * ea + b.acc * eb)


def finish(p: Partial, dtype) -> jnp.ndarray:
    return (p.acc / jnp.maximum(p.l, 1e-30)).astype(dtype)


def _attend_partial(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    valid: jnp.ndarray, sm_scale: float) -> Partial:
    """q [B,Hq,D]; k,v [B,T,Hkv,D] f32; valid [B,T] -> partial."""
    B, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qf, k) * sm_scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)                       # [B,Hkv,g,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhgt,bthd->bhgd", p, v)
    return Partial(m.reshape(B, Hq, 1), l.reshape(B, Hq, 1),
                   acc.reshape(B, Hq, D))


def quantized_attention_partial(q: jnp.ndarray, k_codes, k_scales, v_codes,
                                v_scales, length: jnp.ndarray, *, bits: int,
                                chunk: int, sm_scale: float,
                                paper_mode: bool = False) -> Partial:
    """Attention partial over the compressed prefix.

    fused: chunk-*parallel* flash-decode — every KV chunk computes a local
    softmax partial, then partials merge with a max/sum reduction. Two
    properties matter: (1) where XLA fuses the int4/8 dequant into the
    dot-operand read, HBM bytes = compressed bytes [beyond-paper] (see the
    module docstring for where it does not); (2) the chunk axis
    is born from a reshape of the sequence axis, so a sequence-sharded cache
    (long_500k cells) turns the merge reductions into small cross-device
    all-reduces — sequence-parallel decode attention for free under GSPMD.

    paper: materialize the full bf16 prefix first (the promoted-region write+
    read round trip, optimization_barrier'd so XLA cannot fuse it away), then
    attend uncompressed."""
    B, Hq, D = q.shape
    Sc, Hkv = k_codes.shape[1], k_codes.shape[2]
    chunk = min(chunk, Sc)
    assert Sc % chunk == 0
    nch = Sc // chunk
    g = Hq // Hkv

    if paper_mode:
        k = dequantize_blocks(k_codes, k_scales[..., None], bits, D,
                              jnp.bfloat16)
        v = dequantize_blocks(v_codes, v_scales[..., None], bits, D,
                              jnp.bfloat16)
        # the promoted-region round trip: force materialization
        k, v = jax.lax.optimization_barrier((k, v))
        valid = jnp.arange(Sc)[None, :] < length[:, None]
        return _attend_partial(q, k.astype(jnp.float32),
                               v.astype(jnp.float32), valid, sm_scale)

    resh = lambda a: a.reshape((B, nch, chunk) + a.shape[2:])
    k = dequantize_blocks(resh(k_codes), resh(k_scales)[..., None], bits, D,
                          jnp.float32)                       # [B,n,t,Hkv,D]
    v = dequantize_blocks(resh(v_codes), resh(v_scales)[..., None], bits, D,
                          jnp.float32)
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, D)
    s = jnp.einsum("bhgd,bnthd->bnhgt", qf, k) * sm_scale    # [B,n,Hkv,g,t]
    tpos = (jnp.arange(nch)[:, None] * chunk + jnp.arange(chunk)[None, :])
    valid = tpos[None] < length[:, None, None]               # [B,n,t]
    s = jnp.where(valid[:, :, None, None, :], s, -1e30)
    m_i = jnp.max(s, axis=-1, keepdims=True)                 # [B,n,Hkv,g,1]
    p = jnp.exp(s - m_i)
    l_i = jnp.sum(p, axis=-1, keepdims=True)
    acc_i = jnp.einsum("bnhgt,bnthd->bnhgd", p, v)           # [B,n,Hkv,g,D]
    m = jnp.max(m_i, axis=1, keepdims=True)                  # [B,1,Hkv,g,1]
    w = jnp.exp(m_i - m)
    l = jnp.sum(w * l_i, axis=1)                             # [B,Hkv,g,1]
    acc = jnp.sum(w * acc_i, axis=1)
    return Partial(m[:, 0].reshape(B, Hq, 1), l.reshape(B, Hq, 1),
                   acc.reshape(B, Hq, D))


# ---------------------------------------------------------------------------
# Cache containers (stacked on a leading layer/group axis)
# ---------------------------------------------------------------------------

def init_gqa_cache(cfg: ModelConfig, scfg: ServeConfig, batch: int,
                   max_len: int, n_sites: int) -> Dict[str, jnp.ndarray]:
    Hkv, D = cfg.num_kv_heads, cfg.resolved_head_dim
    W = scfg.hot_window
    bits = scfg.kv_rate_bits
    Dp = D * bits // 8
    z = functools.partial(jnp.zeros)
    return {
        "k_codes": z((n_sites, batch, max_len, Hkv, Dp), jnp.uint8),
        "k_scales": z((n_sites, batch, max_len, Hkv), jnp.float32),
        "v_codes": z((n_sites, batch, max_len, Hkv, Dp), jnp.uint8),
        "v_scales": z((n_sites, batch, max_len, Hkv), jnp.float32),
        "k_hot": z((n_sites, batch, W, Hkv, D), jnp.bfloat16),
        "v_hot": z((n_sites, batch, W, Hkv, D), jnp.bfloat16),
        # boundary between compressed region and hot ring per lane: positions
        # < cold_len live in codes (the pool's per-sequence metadata; lets a
        # resumed request start with an empty ring — promotion is free)
        "cold_len": z((n_sites, batch), jnp.int32),
    }


def init_mla_cache(cfg: ModelConfig, scfg: ServeConfig, batch: int,
                   max_len: int) -> Dict[str, jnp.ndarray]:
    m = cfg.mla or MLAConfig()
    R = m.kv_lora_rank + m.qk_rope_head_dim
    W = scfg.hot_window
    bits = scfg.kv_rate_bits
    Lyr = cfg.num_layers
    z = functools.partial(jnp.zeros)
    return {
        "lat_codes": z((Lyr, batch, max_len, R * bits // 8), jnp.uint8),
        "lat_scales": z((Lyr, batch, max_len), jnp.float32),
        "lat_hot": z((Lyr, batch, W, R), jnp.bfloat16),
        "cold_len": z((Lyr, batch), jnp.int32),
    }


def init_cache(cfg: ModelConfig, scfg: ServeConfig, batch: int,
               max_len: int) -> Dict[str, Any]:
    """Decode cache for any family. Leading axis = layer (or group/site)."""
    if cfg.family == "ssm":
        ssm = cfg.ssm or SSMConfig()
        st = SSM.mamba1_init_state(cfg, batch)
        return {"ssm": jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape).copy(),
            st._asdict())}
    if cfg.family == "hybrid":
        period = cfg.attn_period or cfg.num_layers
        ngroups = cfg.num_layers // period
        st = SSM.mamba2_init_state(cfg, batch)
        ssm_stacked = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(
                a, (ngroups, period) + a.shape).copy(), st._asdict())
        return {"ssm": ssm_stacked,
                **init_gqa_cache(cfg, scfg, batch, max_len, ngroups)}
    if cfg.attn_kind == "mla":
        return init_mla_cache(cfg, scfg, batch, max_len)
    return init_gqa_cache(cfg, scfg, batch, max_len, cfg.num_layers)


def cache_bytes(cache: Dict[str, Any]) -> int:
    import numpy as np
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache))


def cache_axes(cfg: ModelConfig, scfg: ServeConfig) -> Dict[str, Any]:
    """Logical-axis tree mirroring init_cache (for NamedShardings)."""
    gqa = {
        "k_codes": ("layers", "batch", "kv_seq", "kv_heads", None),
        "k_scales": ("layers", "batch", "kv_seq", "kv_heads"),
        "v_codes": ("layers", "batch", "kv_seq", "kv_heads", None),
        "v_scales": ("layers", "batch", "kv_seq", "kv_heads"),
        "k_hot": ("layers", "batch", "kv_hot", "kv_heads", None),
        "v_hot": ("layers", "batch", "kv_hot", "kv_heads", None),
        "cold_len": ("layers", "batch"),
    }
    if cfg.family == "ssm":
        return {"ssm": {"h": ("layers", "batch", "mlp", "state"),
                        "conv": ("layers", "batch", None, "mlp")}}
    if cfg.family == "hybrid":
        # leading axes: [group, period, ...] for ssm; [group, ...] for attn
        return {"ssm": {"h": ("layers", None, "batch", "heads", None, None),
                        "conv": ("layers", None, "batch", None, "mlp")},
                **gqa}
    if cfg.attn_kind == "mla":
        return {"lat_codes": ("layers", "batch", "kv_seq", None),
                "lat_scales": ("layers", "batch", "kv_seq"),
                "lat_hot": ("layers", "batch", "kv_hot", None),
                "cold_len": ("layers", "batch")}
    return gqa


# ---------------------------------------------------------------------------
# Hot-window ring ops
# ---------------------------------------------------------------------------

def _ring_positions(pos: jnp.ndarray, W: int) -> jnp.ndarray:
    """Position stored in each ring slot after inserting token ``pos``:
    p_s = pos - ((pos%W - s) mod W). [B] -> [B, W]."""
    s = jnp.arange(W)[None, :]
    slot_now = (pos % W)[:, None]
    return pos[:, None] - ((slot_now - s) % W)


def _hot_insert(hot: jnp.ndarray, new: jnp.ndarray, pos: jnp.ndarray):
    """hot [B,W,...], new [B,...] inserted at slot pos%W.

    One-hot select instead of scatter: per-batch dynamic scatter indices
    force SPMD into "involuntary full rematerialization" (an all-gather of
    the whole ring per step — measured 640MB/step on llama3 decode, §Perf
    cell A-i3); the masked select partitions cleanly on every axis."""
    W = hot.shape[1]
    onehot = jnp.arange(W)[None, :] == (pos % W)[:, None]        # [B, W]
    m = onehot.reshape(onehot.shape + (1,) * (hot.ndim - 2))
    return jnp.where(m, new[:, None].astype(hot.dtype), hot)


def _hot_read_slot(hot: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Read slot pos%W per batch row via one-hot contraction (exact: the
    mask is 0/1 and each output element sums exactly one bf16 value)."""
    W = hot.shape[1]
    onehot = (jnp.arange(W)[None, :] == (pos % W)[:, None])
    m = onehot.reshape(onehot.shape + (1,) * (hot.ndim - 2))
    return jnp.sum(jnp.where(m, hot.astype(jnp.float32), 0.0), axis=1)


def _evict_to_codes(codes, scales, hot, pos: jnp.ndarray, cold_len: jnp.ndarray,
                    W: int, bits: int):
    """Compress the token aging out of the ring (position pos-W) into the
    compressed region — the streaming clean demotion. Skipped when the slot
    holds no real token (pos < W, or a resumed lane whose older tokens are
    already compressed: pos-W < cold_len)."""
    B = hot.shape[0]
    evict_pos = pos - W
    do = evict_pos >= cold_len
    old = _hot_read_slot(hot, pos)     # [B, Hkv, D] f32 (pre-overwrite!)
    D = old.shape[-1]
    c, s = quantize_blocks(old, bits, D)           # [B,Hkv,Dp], [B,Hkv,1]
    idx = jnp.where(do, jnp.maximum(evict_pos, 0), 0)
    bsel = jnp.arange(B)
    new_codes = codes.at[bsel, idx].set(
        jnp.where(do[:, None, None], c, codes[bsel, idx]))
    new_scales = scales.at[bsel, idx].set(
        jnp.where(do[:, None], s[..., 0], scales[bsel, idx]))
    return new_codes, new_scales


def serve_mlp(params: Params, lp: Params, i, h: jnp.ndarray,
              cfg: ModelConfig) -> jnp.ndarray:
    """Layer ``i``'s MLP on the serve path (prefill and decode): the dense
    SwiGLU, or the expert layer that drops no token."""
    return T.layer_mlp(params, lp, i, h, cfg, L.mlp_apply,
                       lambda p, h, j: MOE.moe_apply_dropless(p, h, cfg, j))


# ---------------------------------------------------------------------------
# Per-layer decode: GQA
# ---------------------------------------------------------------------------

def gqa_decode_layer(lp: Params, x: jnp.ndarray, cache_l: Dict[str, jnp.ndarray],
                     pos: jnp.ndarray, cfg: ModelConfig, scfg: ServeConfig,
                     mlp) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x [B,1,d]; pos [B] current token positions; cache_l holds this layer's
    slices (no leading layer axis); ``mlp(h)`` is the layer's MLP."""
    B = x.shape[0]
    W = scfg.hot_window
    bits = scfg.kv_rate_bits
    D = cfg.resolved_head_dim
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q = L.gqa_project_q(lp["attn"], h, pos[:, None], cfg)[:, 0]   # [B,Hq,D]
    k_new, v_new = L.gqa_project_kv(lp["attn"], h, pos[:, None], cfg)
    k_new, v_new = k_new[:, 0], v_new[:, 0]                       # [B,Hkv,D]

    # demote the token aging out of the hot window (clean by construction)
    cold_len = cache_l["cold_len"]
    kc, ks = _evict_to_codes(cache_l["k_codes"], cache_l["k_scales"],
                             cache_l["k_hot"], pos, cold_len, W, bits)
    vc, vs = _evict_to_codes(cache_l["v_codes"], cache_l["v_scales"],
                             cache_l["v_hot"], pos, cold_len, W, bits)
    k_hot = _hot_insert(cache_l["k_hot"], k_new, pos)
    v_hot = _hot_insert(cache_l["v_hot"], v_new, pos)
    new_cold = jnp.maximum(cold_len, jnp.maximum(pos - W + 1, 0))

    sm = 1.0 / (D ** 0.5)
    cold = quantized_attention_partial(
        q, kc, ks, vc, vs, new_cold, bits=bits, chunk=scfg.attn_chunk,
        sm_scale=sm, paper_mode=not scfg.fused_dequant_attention)
    ring_pos = _ring_positions(pos, W)
    hot_valid = ring_pos >= new_cold[:, None]
    hot = _attend_partial(q, k_hot.astype(jnp.float32),
                          v_hot.astype(jnp.float32), hot_valid, sm)
    o = finish(merge_partials(cold, hot), x.dtype)[:, None]       # [B,1,Hq,D]
    x = x + L.gqa_output(lp["attn"], o, cfg)

    h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + mlp(h2)
    new_cache = dict(cache_l, k_codes=kc, k_scales=ks, v_codes=vc,
                     v_scales=vs, k_hot=k_hot, v_hot=v_hot, cold_len=new_cold)
    return x, new_cache


# ---------------------------------------------------------------------------
# Per-layer decode: MLA (absorbed latent attention over compressed latent)
# ---------------------------------------------------------------------------

def mla_decode_layer(lp: Params, x: jnp.ndarray, cache_l: Dict[str, jnp.ndarray],
                     pos: jnp.ndarray, cfg: ModelConfig, scfg: ServeConfig,
                     mlp) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MLA layer of one decode step: the new token's latent enters the hot
    ring, the token leaving the ring is compressed, and the query attends
    in the latent space (W_UK absorbed into the query, W_UV applied to the
    attended latent) over the ring and the 4-bit latent codes."""
    m = cfg.mla or MLAConfig()
    B = x.shape[0]
    W = scfg.hot_window
    bits = scfg.kv_rate_bits
    hD = cfg.num_heads
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    lat_new = L.mla_latent(lp["attn"], h, pos[:, None], cfg)[:, 0]  # [B,R]

    cold_len = cache_l["cold_len"]
    lc, ls = _evict_latent(cache_l, pos, cold_len, W, bits)
    lat_hot = _hot_insert(cache_l["lat_hot"], lat_new, pos)
    new_cold = jnp.maximum(cold_len, jnp.maximum(pos - W + 1, 0))

    # absorbed query: q_lat [B,H,R_c], q_rope [B,H,rope]
    p = lp["attn"]
    q_nope, q_rope = L.mla_query(p, h, pos[:, None], cfg)
    wkv_b = p["wkv_b"].astype(h.dtype).reshape(
        m.kv_lora_rank, hD, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., :m.qk_nope_head_dim]                 # [R_c, H, nope]
    w_uv = wkv_b[..., m.qk_nope_head_dim:]                 # [R_c, H, v]
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)  # [B,H,R_c]
    q_eff = jnp.concatenate([q_lat, q_rope[:, 0]], axis=-1)  # [B,H,R]
    sm = L.mla_softmax_scale(cfg)

    # latent "KV": key = value = latent vector (head-shared, Hkv=1)
    with spans.scope(spans.MLA_ATTEND):
        cold = quantized_attention_partial(
            q_eff, lc[:, :, None, :], ls[:, :, None], lc[:, :, None, :],
            ls[:, :, None], new_cold, bits=bits, chunk=scfg.attn_chunk,
            sm_scale=sm, paper_mode=not scfg.fused_dequant_attention)
        ring_pos = _ring_positions(pos, W)
        hot_valid = ring_pos >= new_cold[:, None]
        latf = lat_hot.astype(jnp.float32)[:, :, None, :]   # [B,W,1,R]
        hot = _attend_partial(q_eff, latf, latf, hot_valid, sm)
        ctx = finish(merge_partials(cold, hot), jnp.float32)  # [B,H,R]
    ctx_c = ctx[..., :m.kv_lora_rank]
    o = jnp.einsum("bhr,rhv->bhv", ctx_c, w_uv.astype(jnp.float32))
    o = o.reshape(B, 1, hD * m.v_head_dim).astype(x.dtype)
    x = x + o @ p["wo"].astype(x.dtype)

    h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    x = x + mlp(h2)
    new_cache = dict(cache_l, lat_codes=lc, lat_scales=ls, lat_hot=lat_hot,
                     cold_len=new_cold)
    return x, new_cache


def _evict_latent(cache_l, pos, cold_len, W, bits):
    """Latent variant of _evict_to_codes (no head axis: Hkv == 1)."""
    B = cache_l["lat_hot"].shape[0]
    evict_pos = pos - W
    do = evict_pos >= cold_len
    old = _hot_read_slot(cache_l["lat_hot"], pos)          # [B, R]
    R = old.shape[-1]
    c, s = quantize_blocks(old, bits, R)                   # [B,Rp], [B,1]
    idx = jnp.where(do, jnp.maximum(evict_pos, 0), 0)
    bsel = jnp.arange(B)
    codes, scales = cache_l["lat_codes"], cache_l["lat_scales"]
    new_codes = codes.at[bsel, idx].set(
        jnp.where(do[:, None], c, codes[bsel, idx]))
    new_scales = scales.at[bsel, idx].set(
        jnp.where(do, s[..., 0], scales[bsel, idx]))
    return new_codes, new_scales


# ---------------------------------------------------------------------------
# Full decode step (all families)
# ---------------------------------------------------------------------------

def decode_step(params: Params, cache: Dict[str, Any], tokens: jnp.ndarray,
                pos: jnp.ndarray, cfg: ModelConfig, scfg: ServeConfig,
                embeds: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step. tokens [B] int32 (or embeds [B,d]); pos [B].
    Returns (logits [B,V], new cache)."""
    B = tokens.shape[0]
    if cfg.frontend != "none" and embeds is not None:
        x = embeds[:, None].astype(jnp.dtype(cfg.dtype))
    else:
        x = params["tok_embed"].astype(jnp.dtype(cfg.dtype))[tokens][:, None]

    if cfg.family == "ssm":
        def body(x, inp):
            lp, st = inp
            h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
            y, new_st = SSM.mamba1_decode(lp["mixer"], h, SSM.Mamba1State(**st),
                                          cfg)
            return x + y, new_st._asdict()
        x, new_ssm = jax.lax.scan(body, x, (params["layers"], cache["ssm"]))
        logits = T.unembed(params, x, cfg)[:, 0]
        return logits, {"ssm": new_ssm}

    if cfg.family == "hybrid":
        period = cfg.attn_period or cfg.num_layers
        nshared = cfg.attn_shared_blocks

        def gbody(carry, inp):
            x, g = carry
            glp, gcache = inp

            xx = x
            new_ssm = []
            for j in range(period):
                lp_j = jax.tree_util.tree_map(lambda a: a[j], glp)
                st_j = jax.tree_util.tree_map(lambda a: a[j], gcache["ssm"])
                h = L.rms_norm(xx, lp_j["ln"], cfg.norm_eps)
                y, st = SSM.mamba2_decode(lp_j["mixer"], h,
                                          SSM.Mamba2State(**st_j), cfg)
                xx = xx + y
                new_ssm.append(st._asdict())
            new_ssm = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *new_ssm)
            sid = g % nshared
            sp = jax.tree_util.tree_map(lambda a: a[sid], params["shared"])
            attn_cache = {k: gcache[k] for k in
                          ("k_codes", "k_scales", "v_codes", "v_scales",
                           "k_hot", "v_hot", "cold_len")}
            xx, new_attn = gqa_decode_layer(
                sp, xx, attn_cache, pos, cfg, scfg,
                lambda h: L.mlp_apply(sp["mlp"], h))
            return (xx, g + 1), {"ssm": new_ssm, **new_attn}

        (x, _), new_cache = jax.lax.scan(
            gbody, (x, jnp.int32(0)), (params["layers"], cache))
        logits = T.unembed(params, x, cfg)[:, 0]
        return logits, new_cache

    layer_fn = mla_decode_layer if cfg.attn_kind == "mla" else gqa_decode_layer

    def body(x, lp, i, cl):
        return layer_fn(lp, x, cl, pos, cfg, scfg,
                        lambda h: serve_mlp(params, lp, i, h, cfg))

    x, new_cache = T.scan_layers(body, x, params, cfg, cache)
    logits = T.unembed(params, x, cfg)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill: full forward that also fills the cache
# ---------------------------------------------------------------------------

def prefill(params: Params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            scfg: ServeConfig, max_len: int,
            lens: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Run the full prompt [B,S], return (last-token logits, filled cache).

    Prefix tokens older than the hot window are written compressed; the last
    W tokens populate the ring. (The bulk-compression path of the engine.)

    ``lens`` [B] gives each row's true prompt length for right-padded
    batches (length bucketing): the ring holds the last W *real* tokens,
    ``cold_len`` is the real compressed length, and the returned logits are
    each row's last real token's. Padded positions never enter the cache's
    valid range, so a padded row decodes identically to an unpadded one.
    ``lens=None`` means every row is exactly S tokens."""
    x = T.embed(params, batch, cfg)
    B, S, _ = x.shape
    W = scfg.hot_window
    bits = scfg.kv_rate_bits
    pos = jnp.arange(S)[None, :]
    lens_arr = (jnp.full((B,), S, jnp.int32) if lens is None
                else jnp.asarray(lens, jnp.int32))

    def last_logits(x):
        """Per-row logits at the last real token (lens-1)."""
        idx = jnp.clip(lens_arr - 1, 0, S - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)  # [B,1,d]
        return T.unembed(params, x_last, cfg)[:, 0]

    def ring_slots(last):
        """Position held by ring slot s right after token ``last``: the
        largest p <= last with p === s (mod W). [B] -> [B, W]; p < 0 means
        the slot holds no real token (short prompt) — its (clipped-gather)
        content is masked out by decode's hot_valid test."""
        s = jnp.arange(W)[None, :]
        return last[:, None] - ((last[:, None] - s) % W)

    def fill_gqa(k, v):
        """k,v [B,S,Hkv,D] -> cache slices for one site."""
        Hkv, D = k.shape[2], k.shape[3]
        pad = max_len - S
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kc, ks = quantize_blocks(kp, bits, D)
        vc, vs = quantize_blocks(vp, bits, D)
        # ring: last W real tokens, right-aligned at slot p % W
        p = ring_slots(lens_arr - 1)                              # [B, W]
        safe = jnp.clip(p, 0, S - 1)[:, :, None, None]
        k_hot = jnp.take_along_axis(k, safe, axis=1).astype(jnp.bfloat16)
        v_hot = jnp.take_along_axis(v, safe, axis=1).astype(jnp.bfloat16)
        return {"k_codes": kc, "k_scales": ks[..., 0], "v_codes": vc,
                "v_scales": vs[..., 0], "k_hot": k_hot, "v_hot": v_hot,
                "cold_len": jnp.maximum(lens_arr - W, 0)}

    if cfg.family == "ssm":
        def body(x, lp):
            h = L.rms_norm(x, lp["ln"], cfg.norm_eps)
            xz = h @ lp["mixer"]["in_proj"].astype(h.dtype)
            xs, z = jnp.split(xz, 2, axis=-1)
            xc = SSM._causal_conv(xs, lp["mixer"]["conv_w"], lp["mixer"]["conv_b"])
            ssm = cfg.ssm or SSMConfig()
            d_in = ssm.expand * cfg.d_model
            h0 = jnp.zeros((B, d_in, ssm.d_state), jnp.float32)
            y, hT = SSM._mamba1_core(lp["mixer"], xc, z, h0, cfg, True)
            conv_tail = xs[:, -(ssm.d_conv - 1):].astype(jnp.bfloat16)
            return x + y, {"h": hT, "conv": conv_tail}
        x, states = jax.lax.scan(body, x, params["layers"])
        return last_logits(x), {"ssm": states}

    if cfg.family == "hybrid":
        period = cfg.attn_period or cfg.num_layers
        nshared = cfg.attn_shared_blocks
        ssm = cfg.ssm or SSMConfig(kind="mamba2")

        def gbody(carry, glp):
            x, g = carry
            hs, convs = [], []
            for j in range(period):
                lp_j = jax.tree_util.tree_map(lambda a: a[j], glp)
                h = L.rms_norm(x, lp_j["ln"], cfg.norm_eps)
                z, xs, Bc, Cc, dt = SSM._mamba2_split(lp_j["mixer"], h, cfg)
                xc = SSM._causal_conv(xs, lp_j["mixer"]["conv_w"],
                                      lp_j["mixer"]["conv_b"])
                d_in = ssm.expand * cfg.d_model
                H = d_in // ssm.headdim
                h0 = jnp.zeros((B, H, ssm.headdim, ssm.d_state), jnp.float32)
                y, hT = SSM._mamba2_core(lp_j["mixer"], xc, Bc, Cc, dt, z, h0, cfg)
                x = x + y
                hs.append(hT)
                convs.append(xs[:, -(ssm.d_conv - 1):].astype(jnp.bfloat16))
            sid = g % nshared
            sp = jax.tree_util.tree_map(lambda a: a[sid], params["shared"])
            h = L.rms_norm(x, sp["ln1"], cfg.norm_eps)
            k, v = L.gqa_project_kv(sp["attn"], h, pos, cfg)
            q = L.gqa_project_q(sp["attn"], h, pos, cfg)
            o = L.chunked_attention(q, k, v, causal=True)
            x = x + L.gqa_output(sp["attn"], o, cfg)
            h2 = L.rms_norm(x, sp["ln2"], cfg.norm_eps)
            x = x + L.mlp_apply(sp["mlp"], h2)
            site = fill_gqa(k, v)
            site["ssm"] = {"h": jnp.stack(hs), "conv": jnp.stack(convs)}
            return (x, g + 1), site

        (x, _), cache = jax.lax.scan(gbody, (x, jnp.int32(0)), params["layers"])
        return last_logits(x), cache

    if cfg.attn_kind == "mla":
        def body(x, lp, i):
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            lat = L.mla_latent(lp["attn"], h, pos, cfg)        # [B,S,R]
            x = x + L.mla_attend(lp["attn"], h, lat, pos, cfg, causal=True)
            h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            x = x + serve_mlp(params, lp, i, h2, cfg)
            R = lat.shape[-1]
            pad = max_len - S
            latp = jnp.pad(lat, ((0, 0), (0, pad), (0, 0)))
            c, s = quantize_blocks(latp, bits, R)
            p = ring_slots(lens_arr - 1)                          # [B, W]
            safe = jnp.clip(p, 0, S - 1)[:, :, None]
            lat_hot = jnp.take_along_axis(lat, safe, axis=1).astype(
                jnp.bfloat16)
            return x, {"lat_codes": c, "lat_scales": s[..., 0],
                       "lat_hot": lat_hot,
                       "cold_len": jnp.maximum(lens_arr - W, 0)}
        x, cache = T.scan_layers(body, x, params, cfg)
        return last_logits(x), cache

    def body(x, lp, i):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        k, v = L.gqa_project_kv(lp["attn"], h, pos, cfg)
        q = L.gqa_project_q(lp["attn"], h, pos, cfg)
        o = L.chunked_attention(q, k, v, causal=True)
        x = x + L.gqa_output(lp["attn"], o, cfg)
        h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + serve_mlp(params, lp, i, h2, cfg), fill_gqa(k, v)

    x, cache = T.scan_layers(body, x, params, cfg)
    return last_logits(x), cache
