"""Mixture-of-Experts layers (qwen3-moe: 128e top-8; arctic: 128e top-2 +
dense residual; DeepSeek-V2: 64e top-6 + 2 shared experts).

One router (``_route``: float32 scores, softmax, top-k) and two dispatches
over the same parameters:

* ``moe_apply_dropless`` (serving: prefill and decode) drops no token. The
  (token, choice) pairs are sorted by expert and each expert's rows go
  through its three matrices as one grouped product
  (``jax.lax.ragged_dot``), so every routed pair is computed whatever the
  load. It retires the sort-based capacity dispatch (``moe_apply_sorted``)
  that serving used, which dropped the pairs beyond C = ceil(k*N/E*1.25).
* ``moe_apply_grouped`` (training): GShard-style one-hot dispatch in
  groups, truncated at capacity (see its docstring).

Shared experts (DeepSeek-V2) are one SwiGLU of width ``num_shared_experts
* expert_d_ff`` that every token passes through, added to the routed sum;
arctic's dense residual likewise."""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import ModelConfig, MoEConfig
from repro.models.layers import _init, mlp_apply, mlp_init
from repro.obs import spans

CAPACITY_FACTOR = 1.25


def moe_init(key, cfg: ModelConfig) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    mo = cfg.moe or MoEConfig()
    d, f, e = cfg.d_model, mo.expert_d_ff, mo.num_experts
    ks = jax.random.split(key, 5)
    params = {
        "router": _init(ks[0], (d, e), scale=0.02),
        "wi": _init(ks[1], (e, d, f)),
        "wg": _init(ks[2], (e, d, f)),
        "wo": _init(ks[3], (e, f, d), scale=1.0 / (f ** 0.5)),
    }
    axes = {"router": ("fsdp", None), "wi": ("expert", "fsdp", "expert_mlp"),
            "wg": ("expert", "fsdp", "expert_mlp"),
            "wo": ("expert", "expert_mlp", "fsdp")}
    if mo.dense_residual:
        dp, da = mlp_init(ks[4], d, mo.dense_d_ff or cfg.d_ff)
        params["dense"] = dp
        axes["dense"] = da
    if mo.num_shared_experts:
        sp, sa = mlp_init(ks[4], d, mo.num_shared_experts * f)
        params["shared"] = sp
        axes["shared"] = sa
    return params, axes


def _route(p: Dict[str, Any], x: jnp.ndarray, mo: MoEConfig):
    """Router over tokens x [..., D], in float32 as the published gates:
    (probs [..., E], top-k weights [..., k], top-k experts [..., k]). The
    weights are renormalised to 1 where ``norm_topk_prob``, then scaled by
    ``routed_scaling_factor``."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if mo.routed_scaling_factor != 1.0:
        top_p = top_p * mo.routed_scaling_factor
    return probs, top_p, top_i


def _unrouted(p: Dict[str, Any], x: jnp.ndarray, mo: MoEConfig
              ) -> jnp.ndarray:
    """What every token gets beside its routed experts: the shared experts
    and arctic's dense residual (0 where the layer has neither)."""
    out = jnp.zeros_like(x)
    if "shared" in p:
        with spans.scope(spans.MOE_SHARED):
            out = out + mlp_apply(p["shared"], x)
    if mo.dense_residual and "dense" in p:
        out = out + mlp_apply(p["dense"], x)
    return out


GROUP_TOKENS = 512   # grouped dispatch: tokens per routing group


def moe_apply_grouped(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-style grouped einsum dispatch (the GSPMD-native pattern).

    Tokens are viewed as [G, Sg, D] groups (G inherits the batch sharding);
    dispatch/combine are one-hot [G, Sg, E, C] tensors contracted with
    einsums, so the data->expert movement lowers to a clean all-to-all
    instead of the replicating gathers that index-based dispatch costs under
    GSPMD (§Perf cell C: the sort-based path moved ~8x more collective
    bytes). Dispatch-matmul overhead is ~2*k*Sg*cf/d of the expert compute
    (~4% for arctic at Sg=512). Tokens beyond per-group capacity
    C = ceil(k*Sg*cf/E) are dropped (standard GShard semantics)."""
    mo = cfg.moe or MoEConfig()
    B, S, d = x.shape
    e, k = mo.num_experts, mo.top_k
    n = B * S
    sg = min(GROUP_TOKENS, n)
    g = n // sg
    cap = max(1, int(-(-(k * sg * CAPACITY_FACTOR) // e)))

    xg = x.reshape(g, sg, d)
    probs, top_p, top_i = _route(p, xg, mo)                          # [G,Sg,k]

    dispatch = jnp.zeros((g, sg, e, cap), jnp.bool_)
    combine = jnp.zeros((g, sg, e, cap), jnp.float32)
    # running per-(group, expert) fill count threads the k choices
    fill = jnp.zeros((g, e), jnp.int32)
    for j in range(k):
        oh = jax.nn.one_hot(top_i[..., j], e, dtype=jnp.int32)       # [G,Sg,E]
        pos = fill[:, None, :] + jnp.cumsum(oh, axis=1) - oh         # excl.
        keep = (oh > 0) & (pos < cap)
        cslot = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                               dtype=jnp.float32)[..., :cap]         # [G,Sg,E,C]
        sel = keep[..., None] & (cslot > 0)
        dispatch = dispatch | sel
        combine = combine + top_p[..., j][..., None, None] * sel
        fill = fill + jnp.sum(oh, axis=1)

    dsp = dispatch.astype(x.dtype)
    xe = jnp.einsum("gsec,gsd->egcd", dsp, xg)                       # [E,G,C,D]
    h = jax.nn.silu(jnp.einsum("egcd,edf->egcf", xe, p["wg"].astype(x.dtype))) \
        * jnp.einsum("egcd,edf->egcf", xe, p["wi"].astype(x.dtype))
    ye = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(x.dtype))
    out = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), ye)
    out = out.reshape(B, S, d) + _unrouted(p, x, mo)
    frac = jnp.mean(dispatch.any(-1).astype(jnp.float32), axis=(0, 1))
    mprob = jnp.mean(probs, axis=(0, 1))
    aux = jnp.sum(frac * mprob) * e * mo.load_balance_coef
    return out, aux


def moe_apply_dropless(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
                       layer=None) -> jnp.ndarray:
    """Serving: x [B,S,D] -> out [B,S,D], every routed (token, expert) pair
    computed. The pairs are sorted by expert (a stable argsort, so a
    token's rows keep their order within an expert), each expert's
    contiguous rows go through its SwiGLU as grouped products over the
    stacked weights, and the rows return to their pairs and sum with their
    router weights in float32.

    ``layer`` (a traced index): ``p`` holds every MoE layer's parameters
    stacked on a leading axis and this is layer ``layer``'s call. The
    experts' matrices then enter the grouped products whole, as
    ``layers x experts`` groups of which only this layer's hold rows: a
    sliced operand of a grouped product is copied out of the stack first
    (on a v5e, 352 MiB a matrix a layer a step at DeepSeek-V2-Lite's
    widths), an empty group costs nothing."""
    mo = cfg.moe or MoEConfig()
    B, S, d = x.shape
    e, k = mo.num_experts, mo.top_k
    n = B * S
    xf = x.reshape(n, d)
    w = {m: p[m] for m in ("wg", "wi", "wo")}
    if layer is not None:
        n_layers = p["wg"].shape[0]
        w = {m: a.reshape((n_layers * e,) + a.shape[2:]) for m, a in w.items()}
        p = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            {m: a for m, a in p.items() if m not in w})
    with spans.scope(spans.MOE_ROUTE):
        _, top_p, top_i = _route(p, xf, mo)
        flat_e = top_i.reshape(n * k)
        order = jnp.argsort(flat_e, stable=True)
        sizes = jnp.bincount(flat_e, length=e).astype(jnp.int32)
        if layer is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n_layers * e,), jnp.int32), sizes, (layer * e,))
        xs = xf[order // k]                                    # [N*k, D]
    with spans.scope(spans.MOE_EXPERTS):
        g = jax.lax.ragged_dot(xs, w["wg"].astype(x.dtype), sizes)
        u = jax.lax.ragged_dot(xs, w["wi"].astype(x.dtype), sizes)
        ys = jax.lax.ragged_dot(jax.nn.silu(g) * u,
                                w["wo"].astype(x.dtype), sizes)  # [N*k, D]
    with spans.scope(spans.MOE_ROUTE):
        pairs = jnp.zeros_like(ys).at[order].set(ys).reshape(n, k, d)
        out = jnp.einsum("nkd,nk->nd", pairs.astype(jnp.float32), top_p)
    return out.astype(x.dtype).reshape(B, S, d) + _unrouted(p, x, mo)
