"""AdamW with optional IBEX-compressed optimizer state.

``compress_state=True`` stores both Adam moments block-quantized (8-bit m,
8-bit v on a sqrt-companded scale) with per-block f32 scales — the IBEX
qpack compressor applied to training substrate. HBM for optimizer state drops
from 8 bytes/param (2xf32) to ~2.06 bytes/param, exactly the capacity-
expansion story of the paper turned onto the training side. Error behaves
like stochastic-rounding noise on the moments; the cost is two extra qpack
codec passes per step (not measured).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import OptimizerConfig
from repro.core.compressor import dequantize_blocks, quantize_blocks

Params = Any


class AdamState(NamedTuple):
    step: jnp.ndarray
    m: Params            # raw f32 moments, or (codes, scales) when compressed
    v: Params


def _blk(n: int, block: int) -> int:
    return block if n % block == 0 and n >= block else n


def _compress_leaf(x: jnp.ndarray, block: int):
    flat = x.reshape(-1)
    b = _blk(flat.shape[0], block)
    codes, scales = quantize_blocks(flat, 8, b)
    return {"codes": codes, "scales": scales, "block": jnp.int32(b)}


def _decompress_leaf(c, shape, block: int) -> jnp.ndarray:
    b = int(c["block"])
    return dequantize_blocks(c["codes"], c["scales"], 8, b,
                             jnp.float32).reshape(shape)


def init(params: Params, cfg: OptimizerConfig) -> AdamState:
    mdt = jnp.dtype(cfg.moment_dtype)

    # `+ 0` forces a fresh buffer per leaf — m and v must never alias, or
    # donating the optimizer state trips "donate the same buffer twice"
    def zeros_tree():
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, mdt) + jnp.asarray(0, mdt), params)

    if cfg.compress_state:
        comp = lambda t: jax.tree_util.tree_map(
            lambda z: _compress_leaf(z.astype(jnp.float32), cfg.state_block), t)
        return AdamState(jnp.int32(0), comp(zeros_tree()), comp(zeros_tree()))
    return AdamState(jnp.int32(0), zeros_tree(), zeros_tree())


def _lr_at(cfg: OptimizerConfig, step: jnp.ndarray) -> jnp.ndarray:
    warm = jnp.minimum(step.astype(jnp.float32) / max(cfg.warmup_steps, 1), 1.0)
    return cfg.lr * warm


def global_norm(tree: Params) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree_util.tree_leaves(tree)))


def update(grads: Params, state: AdamState, params: Params,
           cfg: OptimizerConfig) -> Tuple[Params, AdamState, Dict[str, jnp.ndarray]]:
    step = state.step + 1
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))
    lr = _lr_at(cfg, step)
    bc1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    is_compressed = cfg.compress_state

    def upd(p, g, m_c, v_c):
        g = g.astype(jnp.float32) * clip
        if is_compressed:
            m = _decompress_leaf(m_c, p.shape, cfg.state_block)
            # v stored on a sqrt-companded scale to preserve dynamic range
            v = _decompress_leaf(v_c, p.shape, cfg.state_block) ** 2
        else:
            m, v = m_c.astype(jnp.float32), v_c.astype(jnp.float32)
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        upd = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - lr * upd).astype(p.dtype)
        if is_compressed:
            return newp, _compress_leaf(m, cfg.state_block), \
                _compress_leaf(jnp.sqrt(v), cfg.state_block)
        mdt = jnp.dtype(cfg.moment_dtype)
        return newp, m.astype(mdt), v.astype(mdt)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, AdamState(step, new_m, new_v), metrics


def state_bytes(state: AdamState) -> int:
    import numpy as np
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(state))
