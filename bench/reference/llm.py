"""Plain reference of the served model: a LLaMA-architecture decoder (the
DeepSeek LLM 7B layout) in float32 ``jax.numpy`` at ``HIGHEST`` matmul
precision, with no cache, no batching and no kernels.

Each layer: RMSNorm, multi-head attention with rotary position embedding
(rotate-half, as the published HF implementation), a residual, RMSNorm, a
SwiGLU MLP and a residual; then a final RMSNorm and an untied output head.

The served system keeps keys and values older than its hot window as 4-bit
block codes (one block per token and head over the head dimension,
symmetric, scale = max |x| / 7). That is part of the configuration's
semantics, so the reference applies it too: a query at position ``p`` sees
the keys and values of positions below ``cold[p]`` through that
quantization and the rest exactly. ``cold`` follows from the request's
length, the hot window and the positions at which it was preempted.

The lower-precision controls: ``mode="int8"`` computes every linear layer
in int8 (weights per output channel, activations per row, int32
accumulation); ``mode="fp8"`` in float8 e4m3 with the same scales and
float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _int8_rows(x):
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _fp8_rows(x):
    """float8 e4m3 with one scale per row (max |x| maps to 448), returned
    as the f32 values it holds."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x, w, mode):
    """x [T, n] f32 @ w [n, m] -> [T, m] f32."""
    if mode == "f32":
        return jnp.dot(x, w.astype(jnp.float32), precision=HI)
    if mode == "fp8":
        wq = _fp8_rows(w.astype(jnp.float32).T).T        # per output col
        return jnp.dot(_fp8_rows(x), wq, precision=HI)
    xq, xs = _int8_rows(x)
    wq, ws = _int8_rows(w.astype(jnp.float32).T)          # per output col
    acc = jnp.dot(xq, wq.T, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws.T


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x [T, H, D]; rotate-half RoPE."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def kv_quantize(x, bits=4):
    """Symmetric block quantization over the last axis:
    (codes int32, scale f32 [..., 1])."""
    qmax = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax - 1, qmax), scale


@functools.partial(jax.jit, static_argnames=("heads", "eps", "theta",
                                             "mode", "qblock"))
def layer(x, lp, cold, *, heads, eps, theta, mode, qblock=512):
    """One decoder layer over a padded sequence x [T, d]; returns the new
    x and this layer's (k, v) [T, H, D] (after RoPE)."""
    T, d = x.shape
    D = d // heads
    pos = jnp.arange(T)
    h = rms_norm(x, lp["ln1"], eps)
    q = rope(linear(h, lp["attn"]["wq"], mode).reshape(T, heads, D), pos,
             theta)
    k = rope(linear(h, lp["attn"]["wk"], mode).reshape(T, heads, D), pos,
             theta)
    v = linear(h, lp["attn"]["wv"], mode).reshape(T, heads, D)
    kc, ks = kv_quantize(k)
    vc, vs = kv_quantize(v)
    kq, vq = kc * ks, vc * vs
    outs = []
    for s in range(0, T, qblock):
        qb = q[s:s + qblock]
        rows = pos[s:s + qblock]
        causal = pos[None, :] <= rows[:, None]
        quant = pos[None, :] < cold[s:s + qblock, None]
        sf = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI)
        sq = jnp.einsum("qhd,khd->hqk", qb, kq, precision=HI)
        sc = jnp.where(quant[None], sq, sf) / np.sqrt(D)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        pq = jnp.where(quant[None], p, 0.0)
        pf = p - pq
        outs.append(jnp.einsum("hqk,khd->qhd", pf, v, precision=HI)
                    + jnp.einsum("hqk,khd->qhd", pq, vq, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(T, d)
    x = x + linear(o, lp["attn"]["wo"], mode)
    h2 = rms_norm(x, lp["ln2"], eps)
    g = linear(h2, lp["mlp"]["wg"], mode)
    u = linear(h2, lp["mlp"]["wi"], mode)
    x = x + linear(jax.nn.silu(g) * u, lp["mlp"]["wo"], mode)
    return x, k, v


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def head(x, norm, w, *, eps, mode):
    return linear(rms_norm(x, norm, eps), w, mode)


def cold_boundaries(prompt_len: int, total: int, parks, window: int
                    ) -> np.ndarray:
    """cold[p] for query positions p < total: prompt positions see exact
    keys (prefill attends before anything is compressed); a decode query
    at p sees codes below max(p - window + 1, last preemption <= p)."""
    cold = np.zeros((total,), np.int32)
    parks = sorted(parks)
    for p in range(prompt_len, total):
        last = max([q for q in parks if q <= p], default=0)
        cold[p] = max(p - window + 1, last, 0)
    return cold


def forward(params, tokens, cold, cfg: dict, mode="f32", rows=None,
            pad_to=1024, keep_kv=False):
    """Logits at ``rows`` (positions) of a sequence, and optionally every
    layer's (k, v) at the real positions."""
    n = len(tokens)
    T = -(-n // pad_to) * pad_to
    tok = np.zeros((T,), np.int32)
    tok[:n] = tokens
    cl = np.zeros((T,), np.int32)
    cl[:n] = cold
    x = params["tok_embed"][jnp.asarray(tok)].astype(jnp.float32)
    cl = jnp.asarray(cl)
    kv = []
    L = params["layers"]["ln1"].shape[0]
    for i in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        x, k, v = layer(x, lp, cl, heads=cfg["num_attention_heads"],
                        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
                        mode=mode)
        if keep_kv:
            kv.append((np.asarray(k[:n]), np.asarray(v[:n])))
    rows = np.arange(n) if rows is None else np.asarray(rows)
    logits = head(x[jnp.asarray(rows)], params["final_norm"],
                  params["lm_head"], eps=cfg["rms_norm_eps"], mode=mode)
    return np.asarray(logits), kv


def served_gap(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at that position."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return best - got


def unpack4(codes: np.ndarray) -> np.ndarray:
    """uint8 [..., D/2] -> int [..., D]: low nibble first, two's complement."""
    lo = (codes & 0xF).astype(np.int16)
    hi = (codes >> 4).astype(np.int16)
    q = np.stack([lo, hi], -1).reshape(codes.shape[:-1] + (-1,))
    return np.where(q >= 8, q - 16, q)


def code_error(codes, scales, ref: np.ndarray) -> float:
    """Largest gap between the parked codes' values and the reference
    values, in quantization steps of the reference's own scale."""
    got = unpack4(codes) * scales[..., None]
    amax = np.abs(ref).max(-1, keepdims=True)
    step = np.where(amax > 0, amax / 7.0, 1.0)
    return float(np.max(np.abs(got - ref) / step))


def pack_codes(kv) -> dict:
    """Per-layer (k, v) [T, H, D] float -> the parked layout: 4-bit codes
    ``{k,v}_codes`` uint8 [L, T, H, D/2] (low nibble first) and scales
    ``{k,v}_scales`` f32 [L, T, H], symmetric with scale max|x| / 7."""
    out = {}
    for j, name in enumerate(("k", "v")):
        codes, scales = [], []
        for layer_kv in kv:
            x = np.asarray(layer_kv[j], np.float32)
            amax = np.abs(x).max(-1)
            s = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
            q = np.clip(np.round(x / s[..., None]), -8, 7).astype(np.int16)
            u = (q & 0xF).astype(np.uint8)
            codes.append(u[..., 0::2] | (u[..., 1::2] << 4))
            scales.append(s)
        out[f"{name}_codes"] = np.stack(codes)
        out[f"{name}_scales"] = np.stack(scales)
    return out


def lose_plane(codes: dict, plane: int = 5) -> dict:
    """A planted fault: of every token's code row (heads x head dimension)
    cut into eight planes, plane ``plane`` reads as code 0, as a pack
    that drops one plane's codes would leave it."""
    out = dict(codes)
    for name in ("k_codes", "v_codes"):
        c = np.array(codes[name])
        flat = c.reshape(c.shape[:2] + (-1,))
        w = flat.shape[-1] // 8
        flat[..., plane * w:(plane + 1) * w] = 0
        out[name] = flat.reshape(c.shape)
    return out
