"""Plain reference of the served model: DeepSeek-V2 (the DeepSeek-V2-Lite
layout) in float32 ``jax.numpy`` at ``HIGHEST`` matmul precision, with no
cache, no batching and no kernels, as the published HF modelling code
computes it.

Each layer: RMSNorm; multi-head latent attention: the query through one
full projection (no q-LoRA) split into 128 "nope" and 64 rotary dims per
head; the token's latent ``h @ W_kv_a`` = [c (512), k_rope (64)], ``c``
through its RMSNorm; keys and values expanded per head from ``c`` by
``W_kv_b`` (not absorbed), the rotary key shared by the heads; YaRN
rotary frequencies; softmax scale ``(nope + rope)^-0.5 * mscale^2`` with
``mscale = 0.1 * mscale_all_dim * ln(factor) + 1``; the output projection
and a residual; RMSNorm; the MLP and a residual. The first
``first_k_dense_replace`` layers' MLP is a dense SwiGLU; the others' is
the expert layer: softmax over the router's float32 logits, the greedy
top-k experts with their scores as weights (renormalised only where
``norm_topk_prob``, times ``routed_scaling_factor``), each a SwiGLU run
over the tokens routed to it, plus the shared experts (one SwiGLU) for
every token. A final RMSNorm and an
untied output head.

Departure: the published model rotates interleaved pairs of the rotary
dims; here rotate-half pairs dim i with dim i + 32. With weights drawn at
random that is a fixed permutation of the rotary columns of the query and
latent projections, and the program uses the same layout.

The served system keeps latents older than its hot window as 4-bit block
codes (one block per token over the 576 latent values, symmetric, scale =
max |x| / 7). That is part of the configuration's semantics, so the
reference applies it too: a query at position ``p`` sees the latents of
positions below ``cold[p]`` through that quantization and the rest
exactly (``llm.cold_boundaries``).

The lower-precision controls compute every linear layer (the router and
the experts too) in int8 or fp8, as ``llm.linear`` does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import llm

HI = llm.HI
linear = llm.linear
rms_norm = llm.rms_norm


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict) -> np.ndarray:
    """HF ``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    def corr_dim(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / rs["factor"]
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def softmax_scale(c: dict) -> float:
    q_dim = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    rs = c.get("rope_scaling") or {}
    s = q_dim ** -0.5
    if rs.get("mscale_all_dim"):
        s *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return s


def rope(x, pos, inv, mag):
    """x [T, H, D]; rotate-half RoPE with inverse frequencies ``inv`` and
    cos/sin scaled by ``mag``."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None] * mag, jnp.sin(ang)[:, None] * mag
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_quantize(lat, bits=4):
    """Dequantized 4-bit block codes of latents [T, R] (one block a token)."""
    q, s = llm.kv_quantize(lat, bits)
    return q * s


def swiglu(x, p, mode):
    return linear(jax.nn.silu(linear(x, p["wg"], mode))
                  * linear(x, p["wi"], mode), p["wo"], mode)


def _shape(c: dict) -> dict:
    rs = c.get("rope_scaling") or {}
    inv = yarn_inv_freq(c["qk_rope_head_dim"], float(c["rope_theta"]), rs) \
        if rs else 1.0 / float(c["rope_theta"]) ** (
            np.arange(0, c["qk_rope_head_dim"], 2) / c["qk_rope_head_dim"])
    mag = yarn_mscale(rs["factor"], rs["mscale"]) / \
        yarn_mscale(rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
    return dict(heads=c["num_attention_heads"], eps=float(c["rms_norm_eps"]),
                nope=c["qk_nope_head_dim"], rdim=c["qk_rope_head_dim"],
                vdim=c["v_head_dim"], rank=c["kv_lora_rank"],
                inv=tuple(float(f) for f in inv), mag=float(mag),
                scale=softmax_scale(c), top_k=c["num_experts_per_tok"],
                norm_topk=bool(c["norm_topk_prob"]),
                route_scale=float(c["routed_scaling_factor"]))


@functools.partial(jax.jit, static_argnames=(
    "heads", "eps", "nope", "rdim", "vdim", "rank", "inv", "mag", "scale",
    "mode", "qblock"))
def attention(x, ap, ln, cold, *, heads, eps, nope, rdim, vdim, rank, inv,
              mag, scale, mode, qblock=512):
    """x + MLA(RMSNorm(x)) over a padded sequence x [T, d]; returns it and
    the layer's latents [T, rank + rope dims]."""
    T = x.shape[0]
    qblock = math.gcd(qblock, T)
    pos = jnp.arange(T)
    h = rms_norm(x, ln, eps)
    q = linear(h, ap["wq"], mode).reshape(T, heads, nope + rdim)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, inv, mag)
    kv_a = linear(h, ap["wkv_a"], mode)
    c = rms_norm(kv_a[:, :rank], ap["kv_norm"], eps)
    k_rope = rope(kv_a[:, None, rank:], pos, inv, mag)[:, 0]
    lat = jnp.concatenate([c, k_rope], -1)

    def expand(lat):
        kv = linear(lat[:, :rank], ap["wkv_b"], mode).reshape(
            T, heads, nope + vdim)
        return kv[..., :nope], kv[..., nope:], lat[:, rank:]
    kn_f, v_f, kr_f = expand(lat)
    kn_q, v_q, kr_q = expand(latent_quantize(lat))

    def block(s):
        rows = s + jnp.arange(qblock)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, s, qblock)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, s, qblock)
        causal = pos[None, :] <= rows[:, None]
        quant = pos[None, :] < jax.lax.dynamic_slice_in_dim(
            cold, s, qblock)[:, None]

        def scores(kn, kr):
            return (jnp.einsum("qhd,khd->hqk", qn, kn, precision=HI)
                    + jnp.einsum("qhd,kd->hqk", qr, kr, precision=HI))
        sc = jnp.where(quant[None], scores(kn_q, kr_q),
                       scores(kn_f, kr_f)) * scale
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        pq = jnp.where(quant[None], p, 0.0)
        return (jnp.einsum("hqk,khd->qhd", p - pq, v_f, precision=HI)
                + jnp.einsum("hqk,khd->qhd", pq, v_q, precision=HI))
    outs = jax.lax.map(block, jnp.arange(0, T, qblock))
    o = outs.reshape(T, heads * vdim)
    return x + linear(o, ap["wo"], mode), lat


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def dense_mlp(x, mp, ln, *, eps, mode):
    return x + swiglu(rms_norm(x, ln, eps), mp, mode)


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk", "route_scale", "mode"))
def route(x, mp, ln, *, eps, top_k, norm_topk, route_scale, mode):
    """RMSNorm(x), each token's top-k experts and their weights: softmax
    over the router's logits, renormalised only where ``norm_topk``, times
    ``routed_scaling_factor``; and x plus the shared experts' output."""
    h = rms_norm(x, ln, eps)
    probs = jax.nn.softmax(linear(h, mp["router"], mode), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return h, top_p * route_scale, top_i, x + swiglu(h, mp["shared"], mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def experts(acc, h, rows, w, wg, wi, wo, *, mode):
    """acc plus, expert by expert, ``w[e] * SwiGLU_e(h[rows[e]])`` added at
    ``rows[e]``: rows [E, C] are the tokens routed to expert e, padded with
    token 0 at weight 0."""
    def one(acc, e):
        y = swiglu(h[rows[e]], {"wg": wg[e], "wi": wi[e], "wo": wo[e]}, mode)
        return acc.at[rows[e]].add(w[e][:, None] * y), None
    return jax.lax.scan(one, acc, jnp.arange(rows.shape[0]))[0]


def expert_mlp(x, mp, ln, n, *, eps, top_k, norm_topk, route_scale, mode):
    """x + MoE(RMSNorm(x)), expert by expert as the published ``moe_infer``
    loops: each expert runs over the tokens routed to it, and its output
    is added to them with their weight for it. Only the first ``n`` rows
    (the sequence; the rest is padding) are routed. Every expert's rows
    are padded to the same power of two (at least 256), so that few shapes
    compile."""
    h, top_p, top_i, acc = route(x, mp, ln, eps=eps, top_k=top_k,
                                 norm_topk=norm_topk, route_scale=route_scale,
                                 mode=mode)
    top_p, top_i = np.asarray(top_p)[:n], np.asarray(top_i)[:n]
    n_exp = mp["router"].shape[-1]
    picked = [np.nonzero(top_i == e) for e in range(n_exp)]
    cap = 1 << max(max(len(t) for t, _ in picked) - 1, 255).bit_length()
    rows = np.zeros((n_exp, cap), np.int32)
    w = np.zeros((n_exp, cap), np.float32)
    for e, (tok, slot) in enumerate(picked):
        rows[e, :len(tok)], w[e, :len(tok)] = tok, top_p[tok, slot]
    return experts(acc, h, rows, w, mp["wg"], mp["wi"], mp["wo"], mode=mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head(x, rows, norm, w, *, eps, mode):
    return linear(rms_norm(x[rows], norm, eps), w, mode)


@jax.jit
def embed(table, tok):
    return table[tok].astype(jnp.float32)


@jax.jit
def layer(tree, i):
    """Layer ``i``'s slice of a stacked parameter tree (``i`` traced, so one
    program serves every layer)."""
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def forward(params, tokens, cold, c: dict, mode="f32", rows=None,
            pad_to=4096, keep_lat=False):
    """Logits at ``rows`` (positions) of a sequence, and optionally every
    layer's latents [n, rank + rope dims] at the real positions. ``params``
    is the program's tree (``layers`` holds attention and norms, then
    ``dense_mlp`` and ``moe`` the MLPs); ``c`` the configuration file.
    The sequence is padded to a multiple of ``pad_to`` tokens and the rows
    to one of 128, so that few shapes compile."""
    sh = _shape(c)
    n = len(tokens)
    T = -(-n // pad_to) * pad_to
    tok = np.zeros((T,), np.int32)
    tok[:n] = tokens
    cl = np.zeros((T,), np.int32)
    cl[:n] = cold
    x = embed(params["tok_embed"], tok)
    k = c["first_k_dense_replace"]
    lats = []
    for i in range(c["num_hidden_layers"]):
        lp = layer(params["layers"], i)
        x, lat = attention(x, lp["attn"], lp["ln1"], cl, mode=mode,
                           **{key: sh[key] for key in (
                               "heads", "eps", "nope", "rdim", "vdim", "rank",
                               "inv", "mag", "scale")})
        if i < k:
            x = dense_mlp(x, layer(params["dense_mlp"], i), lp["ln2"],
                          eps=sh["eps"], mode=mode)
        else:
            x = expert_mlp(x, layer(params["moe"], i - k), lp["ln2"], n,
                           eps=sh["eps"], top_k=sh["top_k"],
                           norm_topk=sh["norm_topk"],
                           route_scale=sh["route_scale"], mode=mode)
        if keep_lat:
            lats.append(np.asarray(lat)[:n])
    rows = np.arange(n) if rows is None else np.asarray(rows)
    padded = np.zeros((-(-len(rows) // 128) * 128,), np.int32)
    padded[:len(rows)] = rows
    logits = head(x, padded, params["final_norm"], params["lm_head"],
                  eps=sh["eps"], mode=mode)
    return np.asarray(logits)[:len(rows)], lats


def pack_latents(lats) -> dict:
    """Per-layer latents [T, R] float -> the parked layout: ``lat_codes``
    uint8 [L, T, R/2] (low nibble first), ``lat_scales`` f32 [L, T]."""
    codes, scales = [], []
    for lat in lats:
        x = np.asarray(lat, np.float32)
        amax = np.abs(x).max(-1)
        s = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
        q = np.clip(np.round(x / s[..., None]), -8, 7).astype(np.int16)
        u = (q & 0xF).astype(np.uint8)
        codes.append(u[..., 0::2] | (u[..., 1::2] << 4))
        scales.append(s)
    return {"lat_codes": np.stack(codes), "lat_scales": np.stack(scales)}


def lose_plane(codes: dict, plane: int = 5) -> dict:
    """A planted fault: of every token's latent code row cut into eight
    planes, plane ``plane`` reads as code 0."""
    c = np.array(codes["lat_codes"])
    w = c.shape[-1] // 8
    c[..., plane * w:(plane + 1) * w] = 0
    return dict(codes, lat_codes=c)
