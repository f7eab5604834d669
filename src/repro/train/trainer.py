"""Train-step factory: microbatched, remat'd, sharded, optionally with
error-feedback compressed gradient collectives.

Two gradient-reduction modes:
  * GSPMD (default): params are FSDP-sharded over "data" (logical "fsdp"
    axis); XLA emits the optimal reduce-scatter/all-gather pair per layer,
    overlapped with the scan-over-layers compute.
  * compressed DP (ocfg.compress_grads): for replicated-param data-parallel
    runs, the cross-device mean is done manually inside shard_map as
    psum_scatter(f32) + int8 all-gather with error feedback —
    ~1.8x fewer wire bytes than a ring all-reduce (the collective roofline
    term).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import sharding as SH
from repro.common.types import ModelConfig, OptimizerConfig, TrainConfig
from repro.models import transformer as T
from repro.optim import adamw, gradcomp

Tree = Any


def _microbatch(batch: Dict[str, jnp.ndarray], k: int) -> Dict[str, jnp.ndarray]:
    return jax.tree_util.tree_map(
        lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)


def grads_and_loss(params: Tree, batch: Dict[str, jnp.ndarray],
                   cfg: ModelConfig, microbatches: int
                   ) -> Tuple[Tree, jnp.ndarray]:
    """Microbatched grad accumulation via lax.scan (constant live memory)."""
    if microbatches <= 1:
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg)[0])(params)
        return grads, loss

    mbs = _microbatch(batch, microbatches)

    def body(carry, mb):
        acc, loss_acc = carry
        loss, g = jax.value_and_grad(
            lambda p: T.loss_fn(p, mb, cfg)[0])(params)
        acc = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(jnp.float32), acc, g)
        return (acc, loss_acc + loss), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gsum, lsum), _ = jax.lax.scan(body, (zeros, jnp.float32(0)), mbs)
    inv = 1.0 / microbatches
    return jax.tree_util.tree_map(lambda g: g * inv, gsum), lsum * inv


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    mesh: Optional[Mesh] = None,
                    rules=SH.DEFAULT_RULES,
                    param_axes: Optional[Tree] = None):
    """Returns (train_step, shardings dict). Without a mesh: plain jit."""
    ocfg = tcfg.optimizer

    def step(params: Tree, opt: adamw.AdamState, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[Tree, adamw.AdamState, Dict[str, jnp.ndarray]]:
        grads, loss = grads_and_loss(params, batch, cfg, tcfg.microbatches)
        new_params, new_opt, metrics = adamw.update(grads, opt, params, ocfg)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1)), None

    assert param_axes is not None
    p_shard = SH.tree_shardings(mesh, param_axes, rules)
    opt_shard = adamw.AdamState(
        step=NamedSharding(mesh, P()),
        m=_opt_tree_shardings(p_shard, ocfg, mesh),
        v=_opt_tree_shardings(p_shard, ocfg, mesh))
    batch_spec = NamedSharding(mesh, SH.logical_to_spec(
        ("batch", "seq"), rules, mesh.axis_names))
    batch_shard = {"tokens": batch_spec, "labels": batch_spec}
    if cfg.frontend != "none":
        batch_shard["embeds"] = NamedSharding(mesh, SH.logical_to_spec(
            ("batch", "seq", "embed"), rules, mesh.axis_names))
    metrics_shard = {k: NamedSharding(mesh, P()) for k in
                     ("loss", "grad_norm", "lr")}
    fn = jax.jit(step,
                 in_shardings=(p_shard, opt_shard, batch_shard),
                 out_shardings=(p_shard, opt_shard, metrics_shard),
                 donate_argnums=(0, 1))
    return fn, {"params": p_shard, "opt": opt_shard, "batch": batch_shard}


def _opt_tree_shardings(p_shard: Tree, ocfg: OptimizerConfig, mesh: Mesh):
    """Moment shardings mirror params; compressed moments are replicated
    blobs (codes/scales flattened — sharded by fsdp is possible but the
    compressed footprint is small enough to keep simple)."""
    if not ocfg.compress_state:
        return p_shard
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda s: {"codes": rep, "scales": rep, "block": rep}, p_shard)


# ---------------------------------------------------------------------------
# Compressed-collective DP step (replicated params) via shard_map.
# ---------------------------------------------------------------------------

def make_dp_compressed_step(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh,
                            axis: str = "data"):
    """Data-parallel step with int8 error-feedback gradient collectives.

    Params replicated; batch sharded on ``axis``. Per step and per device the
    wire traffic is size(f32)·(N-1)/N (psum_scatter) + size/4 (int8
    all-gather) ≈ 1.25x size vs 2x size for a ring all-reduce."""
    ocfg = tcfg.optimizer
    ndev = 1
    for ax, sz in zip(mesh.axis_names, mesh.devices.shape):
        if ax == axis:
            ndev = sz

    def step(params, opt, residual, batch):
        def inner(params, opt, residual, batch):
            grads, loss = grads_and_loss(params, batch, cfg, tcfg.microbatches)
            loss = jax.lax.pmean(loss, axis)

            def reduce_one(g, r):
                """g leaf; r [1, n] this device's error-feedback residual."""
                gf = g.astype(jnp.float32)
                flat = gf.reshape(-1)
                n = flat.shape[0]
                if n % ndev or n < 4 * ndev:      # tiny leaves: plain psum
                    return jax.lax.pmean(gf, axis), r
                ns = n // ndev
                shard = jax.lax.psum_scatter(flat, axis, scatter_dimension=0,
                                             tiled=True) / ndev
                rs = r[0, :ns]
                blk = gradcomp._block_for(ns, 512)
                c = gradcomp.compress_leaf(shard + rs, blk)
                back = gradcomp.decompress_leaf(c, (ns,), blk)
                new_r = r.at[0, :ns].set(shard + rs - back)
                codes = jax.lax.all_gather(c["codes"], axis, tiled=True)
                scales = jax.lax.all_gather(c["scales"], axis, tiled=True)
                full = gradcomp.decompress_leaf(
                    {"codes": codes, "scales": scales}, (n,), blk)
                return full.reshape(g.shape), new_r

            flat_g, treedef = jax.tree_util.tree_flatten(grads)
            flat_r = treedef.flatten_up_to(residual)
            out = [reduce_one(g, r) for g, r in zip(flat_g, flat_r)]
            grads = treedef.unflatten([o[0] for o in out])
            residual = treedef.unflatten([o[1] for o in out])
            new_params, new_opt, metrics = adamw.update(grads, opt, params, ocfg)
            metrics["loss"] = loss
            return new_params, new_opt, residual, metrics

        rep = P()
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(rep, rep, P(axis), P(axis)),
            out_specs=(rep, rep, P(axis), rep),
            check_vma=False)(params, opt, residual, batch)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def init_residual_flat(params: Tree, ndev: int) -> Tree:
    """Per-device EF residuals: [ndev, size] leaves, sharded on the DP axis."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((ndev, p.size), jnp.float32), params)
