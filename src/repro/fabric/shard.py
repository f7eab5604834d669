"""Sharded fabric execution: the stacked pool pytree on a real device
mesh (DESIGN.md §17).

The vmapped drivers in ``fabric/replay.py`` simulate "N expanders" as one
stacked array on ONE device — modeled delivered time scales, wall-clock
does not. This module runs the same computation ``shard_map``-ed over the
``expander`` mesh axis (``common.sharding.expander_mesh``): each device
owns an equal block of ``L = N / D`` expanders and replays its shard with
the SAME vmapped ``batch._replay_windows_masked`` window bodies, so
per-expander counters are bit-identical to the single-device vmap oracle
(all pool state is integer; asserted by tests/test_fabric_sharded.py and
every benchmarks/fabric_bench.py sharded point).

Three pieces:

  * ``plan_in_jit``      — the ``MigrationPolicy`` plan step as a pure
    jittable function over the in-jit ``segment_stats`` facts, mirroring
    ``SpillPressure._pressure_moves`` / ``TrafficRebalance.plan`` move
    for move (same candidate order, same donor accounting, same urgency
    rule), so the per-segment ``_fetch_view`` host fetch becomes optional
    telemetry instead of a control dependency;
  * ``collective_apply`` — one migration epoch as collective page motion:
    per move, ONE ``lax.psum`` broadcasts the source's metadata entry and
    the destination's live allocation-headroom bit (dynamic src/dst ranks
    cannot use ``ppermute``'s static permutations), and the compressed
    payload rides a ``lax.ppermute`` ring — log2(D) unconditional
    rotation stages selected by the bits of the replicated (dst - src)
    rotation amount. All collectives sit OUTSIDE ``lax.cond``; the conds
    guard only local slice updates (the ``migrate_src`` / ``migrate_dst``
    halves ``fabric.ops.migrate_page`` itself is composed from), keeping
    the apply bit-identical to the host-planned ``apply_migrations``;
  * ``replay_step`` / ``boundary_step`` — lru-cached jitted
    ``shard_map`` builders the ``Fabric`` sharded driver calls: a plain
    sharded segment replay (migration off), and the fused
    replay → all_gather stats → plan → collective-apply boundary whose
    outcome the host fetches in ONE sync (``Fabric._commit_boundary``).

Planner parity note: all pool state and spill logic is integer, so the
``spill`` policy plans bit-identically to the host planner. The
``rebalance`` time trigger compares float32 device times where the host
compares float64 promotions of the same float32 values — equivalent
except at exact ties of ``time_ratio * times[cold]``, which the parity
tests script away from.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.sharding import EXPANDER_AXIS
from repro.common.types import PoolConfig
from repro.core.engine import batch as B
from repro.core.engine import ops
from repro.core.engine.policy import Policy
from repro.core.engine.state import C_HOST_RD, C_HOST_WR, Pool
from repro.fabric import migration as MG
from repro.fabric import ops as fops
from repro.simx import time as TM


def plan_params(policy: "MG.MigrationPolicy") -> Tuple:
    """Hashable planner parameters for the jit cache (``MigrationPolicy``
    dataclasses are unhashable). ``kind`` selects the in-jit planner."""
    if isinstance(policy, MG.TrafficRebalance):
        return ("rebalance", policy.k, policy.low, policy.proactive,
                policy.trigger, policy.time_ratio, policy.min_delta)
    if isinstance(policy, MG.SpillPressure):
        return ("spill", policy.k, policy.low, policy.proactive)
    raise ValueError(f"no in-jit planner for {policy.name!r}")


def plan_rows(params: Tuple, n_expanders: int) -> int:
    """Plan rows: one per potential pressure source, plus the rebalance
    row. Row-major flattening preserves the host planner's move order
    (ascending starved expander, rebalance last)."""
    return n_expanders + (1 if params[0] == "rebalance" else 0)


def plan_in_jit(params: Tuple, free_units, free_singles, free_groups,
                eligible, referenced, delta, times, blocked):
    """The MigrationPolicy plan step, jittable: mirrors
    ``SpillPressure._pressure_moves`` (+ the ``TrafficRebalance``
    traffic trigger) over the in-jit stats. Returns ``(pages, srcs,
    dsts, urgent)`` with pages int32[R, k] -1-padded per row — a row per
    potential source expander in ascending order (the host loop's order)
    plus the rebalance row, so the flattened real moves sequence exactly
    as the host plan's concatenation.

    ``recent`` is omitted: the synchronous scheduling the sharded driver
    uses never carries recently-moved pages (``_replay_sync`` passes
    zeros), and ``blocked`` plays the livelock-guard role."""
    kind, k = params[0], int(params[1])
    low, proactive = int(params[2]), float(params[3])
    n, n_pages = eligible.shape
    free0 = free_units.astype(jnp.int32)
    donor_ok = (free_singles >= 7) & (free_groups >= 1)
    # the trigger set is fixed from the ORIGINAL headroom (the host loop
    # computes np.nonzero before any donor decrement)
    trig = free0 < proactive * low
    cand_all = eligible & ~blocked[None, :]
    rows = plan_rows(params, n)
    pages0 = jnp.full((rows, k), -1, jnp.int32)
    srcs0 = jnp.zeros((rows, k), jnp.int32)
    dsts0 = jnp.zeros((rows, k), jnp.int32)
    lane = jnp.arange(k, dtype=jnp.int32)

    def body(e, carry):
        free, urgent, pages, srcs, dsts = carry
        donor = jnp.argmax(free).astype(jnp.int32)
        cand = cand_all[e]
        cnt = jnp.minimum(cand.sum(), k).astype(jnp.int32)
        ok = trig[e] & (donor != e) & (free[donor] >= 2 * low) & \
            donor_ok[donor] & (cnt > 0)
        idx = jnp.nonzero(cand, size=k, fill_value=n_pages)[0] \
            .astype(jnp.int32)
        urgent = urgent | (ok & (free[e] < low))
        pages = pages.at[e].set(jnp.where(ok & (lane < cnt), idx, -1))
        srcs = srcs.at[e].set(jnp.full((k,), e, jnp.int32))
        dsts = dsts.at[e].set(jnp.full((k,), donor, jnp.int32))
        # conservative donor accounting within one plan (8 units/page)
        free = free.at[donor].add(jnp.where(ok, -8 * cnt, 0))
        return free, urgent, pages, srcs, dsts

    free, urgent, pages, srcs, dsts = lax.fori_loop(
        0, n, body, (free0, jnp.asarray(False), pages0, srcs0, dsts0))

    if kind == "rebalance" and n > 1:
        trigger, time_ratio = float(params[4]), float(params[5])
        min_delta = int(params[6])
        host_d = delta[:, C_HOST_RD] + delta[:, C_HOST_WR]
        total = host_d.sum()
        hot = jnp.argmax(host_d).astype(jnp.int32)
        ok_d = (free >= 2 * low) & donor_ok
        ok_d = ok_d.at[hot].set(False)
        fire = (total >= min_delta) & ok_d.any() & \
            (host_d[hot] * n > trigger * total)
        cold = jnp.argmin(jnp.where(ok_d, times, jnp.inf)).astype(jnp.int32)
        fire = fire & (times[hot] > time_ratio * times[cold])
        # pages the pressure moves already claimed are off the table
        claimed = jnp.zeros((n_pages + 1,), bool).at[
            jnp.where(pages >= 0, pages, n_pages).reshape(-1)].set(True)
        cand = cand_all[hot] & ~claimed[:n_pages]
        refd = cand & referenced[hot]
        # referenced-first, then remaining candidates, each in page order:
        # a stable argsort over the 3-level rank reproduces the host's
        # concatenated np.nonzero ordering exactly
        rank = jnp.where(refd, 0, jnp.where(cand, 1, 2)).astype(jnp.int32)
        order = jnp.argsort(rank, stable=True).astype(jnp.int32)[:k]
        cnt = jnp.minimum(cand.sum(), k).astype(jnp.int32)
        fire = fire & (cnt > 0)
        pages = pages.at[n].set(jnp.where(fire & (lane < cnt), order, -1))
        srcs = srcs.at[n].set(jnp.full((k,), hot, jnp.int32))
        dsts = dsts.at[n].set(jnp.full((k,), cold, jnp.int32))
    return pages, srcs, dsts, urgent


def collective_apply(stack_l: Pool, cfg: PoolConfig, policy: Policy,
                     pages, srcs, dsts, n_local: int, n_devices: int
                     ) -> Tuple[Pool, jnp.ndarray]:
    """One migration epoch on the LOCAL pool shard [L, ...] inside a
    ``shard_map`` over the expander axis; ``pages``/``srcs``/``dsts``
    are the replicated flattened plan (int32[K], pages -1-padded).

    Per move: the source's metadata entry and the destination's live
    headroom bit cross the mesh in ONE fused psum of masked
    contributions; the payload rides the ppermute ring (skipped entirely
    when ``cfg.store_payload`` is off — the simx pools carry no bytes);
    the eligibility / headroom / guard conjunction is exactly
    ``apply_migrations``', and the serial fori order is preserved, so
    the result is bit-identical to the host-planned apply. Returns the
    updated shard plus the replicated int32[K] moved OSPNs (-1 where
    skipped)."""
    rank = lax.axis_index(EXPANDER_AXIS).astype(jnp.int32)
    mw = stack_l.meta.shape[-1]

    def body(i, carry):
        stack, moved = carry
        p, s, d = pages[i], srcs[i], dsts[i]
        pc = jnp.maximum(p, 0)
        sdev, sloc = s // n_local, s % n_local
        ddev, dloc = d // n_local, d % n_local
        is_src = sdev == rank
        is_dst = ddev == rank
        entry_l = stack.meta[sloc, pc]
        head_l = (stack.cfree.top[dloc] >= 7) & (stack.gfree.top[dloc] >= 1)
        # one psum broadcasts entry (from src) + headroom bit (from dst)
        vec = jnp.concatenate([
            jnp.where(is_src, entry_l, jnp.zeros_like(entry_l)),
            jnp.where(is_dst & head_l, jnp.uint32(1), jnp.uint32(0))[None]])
        vec = lax.psum(vec, EXPANDER_AXIS)
        entry, headroom = vec[:mw], vec[mw] > 0
        eligible, nchunks = fops.page_eligible(entry)
        ok = (p >= 0) & (s != d) & headroom & eligible
        if cfg.store_payload:
            src_pool = jax.tree_util.tree_map(lambda a: a[sloc], stack)
            buf = ops._gather_page_buf(src_pool, cfg, entry)
            buf = jnp.where(is_src, buf, jnp.zeros_like(buf))
            # ppermute needs a STATIC permutation; the dynamic src->dst
            # route decomposes into log2(D) fixed +2^b ring rotations,
            # each taken iff that bit of the replicated rotation is set
            rot = jnp.mod(ddev - sdev, n_devices)
            for b in range((n_devices - 1).bit_length()):
                perm = [(j, (j + (1 << b)) % n_devices)
                        for j in range(n_devices)]
                shifted = lax.ppermute(buf, EXPANDER_AXIS, perm)
                take = ((rot >> b) & 1).astype(bool)
                buf = jnp.where(take, shifted, buf)
        else:
            buf = jnp.zeros((cfg.page_bytes,), jnp.uint8)

        def upd_src(sl):
            sp = jax.tree_util.tree_map(lambda a: a[sloc], sl)
            sp = fops.migrate_src(sp, cfg, policy, pc, entry, nchunks)
            return jax.tree_util.tree_map(
                lambda a, x: a.at[sloc].set(x), sl, sp)

        def upd_dst(sl):
            dp = jax.tree_util.tree_map(lambda a: a[dloc], sl)
            dp = fops.migrate_dst(dp, cfg, policy, pc, entry, nchunks, buf)
            return jax.tree_util.tree_map(
                lambda a, x: a.at[dloc].set(x), sl, dp)

        stack = lax.cond(ok & is_src, upd_src, lambda sl: sl, stack)
        stack = lax.cond(ok & is_dst, upd_dst, lambda sl: sl, stack)
        moved = moved.at[i].set(jnp.where(ok, p, -1))
        return stack, moved

    moved0 = jnp.full(pages.shape, -1, jnp.int32)
    return lax.fori_loop(0, pages.shape[0], body, (stack_l, moved0))


def _local_replay(pools_l, cfg, policy, o, w, b, v, lanes_l, pending):
    """The per-shard segment replay: the SAME vmap composition the
    single-device ``_replay_stacked`` runs over all N expanders, over
    the local L — hence bit-identity per expander."""
    pools_l = jax.vmap(
        lambda p, oo, ww, bb, vv: B._replay_windows_masked(
            p, cfg, policy, oo, ww, bb, vv, pending,
            # XLA:CPU miscompiles the fori/while slow drain inside
            # shard_map manual regions on devices != 0 (batch._window_step)
            unroll_slow=True)
    )(pools_l, o, w, b, v)
    times_l = jax.vmap(TM.exec_time_vec)(pools_l.counters, lanes_l)
    return pools_l, times_l


@functools.lru_cache(maxsize=None)
def replay_step(mesh: Mesh, cfg: PoolConfig, policy: Policy,
                need_free: bool):
    """Jitted shard_map segment replay (migration off): returns
    ``(pools, times[, free_units])``, every output sharded over the
    expander axis — the host fetches nothing per segment (the deferred
    ``Fabric._drain_deferred`` fetch prices the run afterwards)."""
    ax = P(EXPANDER_AXIS)

    def local(pools_l, o, w, b, v, lanes_l, pending):
        pools_l, times_l = _local_replay(pools_l, cfg, policy,
                                         o, w, b, v, lanes_l, pending)
        if not need_free:
            return pools_l, times_l
        stats_l = jax.vmap(lambda p: fops.segment_stats(p, cfg))(pools_l)
        return pools_l, times_l, stats_l.free_units

    outs = (ax, ax) if not need_free else (ax, ax, ax)
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(ax, ax, ax, ax, ax, ax, P()),
        out_specs=outs, check_vma=False))


@functools.lru_cache(maxsize=None)
def boundary_step(mesh: Mesh, cfg: PoolConfig, policy: Policy,
                  mparams: Tuple, n_expanders: int):
    """Jitted shard_map replay + in-jit plan + collective apply: one
    segment boundary in ONE dispatch, no host round-trip between the
    stats and the epoch. Outputs, in order:

      pools       sharded   post-apply stack
      times       sharded   float32[N] post-replay delivered seconds
      ctrs_mid    sharded   [N, C] post-replay / pre-apply counters
      free_pre    sharded   int32[N] post-replay headroom (chunk units)
      fc, fg      sharded   int32[N] post-apply freelist tops
      pages/srcs/dsts  replicated  the flattened plan (pages -1-padded)
      urgent      replicated  bool
      moved       replicated  int32[K] applied OSPNs (-1 where skipped)

    The host commit (``Fabric._commit_boundary``) fetches the lot —
    plus the returned pools' counters — in one ``jax.device_get``: one
    sync per boundary, versus the pipelined driver's one per segment
    PLUS one per epoch."""
    n_dev = mesh.devices.size
    if n_expanders % n_dev:
        raise ValueError(f"{n_expanders} expanders not divisible by "
                         f"{n_dev} devices")
    n_local = n_expanders // n_dev
    ax = P(EXPANDER_AXIS)

    def local(pools_l, o, w, b, v, lanes_l, pending, blocked):
        ctrs_prev_l = pools_l.counters
        pools_l, times_l = _local_replay(pools_l, cfg, policy,
                                         o, w, b, v, lanes_l, pending)
        stats_l = jax.vmap(lambda p: fops.segment_stats(p, cfg))(pools_l)
        ctrs_mid_l = pools_l.counters
        delta_l = ctrs_mid_l - ctrs_prev_l

        def gather(x):
            return lax.all_gather(x, EXPANDER_AXIS, tiled=True)

        # replicate the planner's view: every device plans identically
        pages, srcs, dsts, urgent = plan_in_jit(
            mparams, gather(stats_l.free_units),
            gather(stats_l.free_singles), gather(stats_l.free_groups),
            gather(stats_l.eligible), gather(stats_l.referenced),
            gather(delta_l), gather(times_l), blocked)
        pools_l, moved = collective_apply(
            pools_l, cfg, policy, pages.reshape(-1), srcs.reshape(-1),
            dsts.reshape(-1), n_local, n_dev)
        return (pools_l, times_l, ctrs_mid_l, stats_l.free_units,
                pools_l.cfree.top, pools_l.gfree.top,
                pages, srcs, dsts, urgent, moved)

    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(ax, ax, ax, ax, ax, ax, P(), P()),
        out_specs=(ax, ax, ax, ax, ax, ax, P(), P(), P(), P(), P()),
        check_vma=False))


def shard_pools(pools: Pool, mesh: Mesh) -> Pool:
    """Place a stacked pool pytree with its leading expander axis sharded
    over the mesh (host->device placement, not a sync)."""
    sh = NamedSharding(mesh, P(EXPANDER_AXIS))
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), pools)


def device_of_expander(n_expanders: int, n_devices: int) -> np.ndarray:
    """int [N]: which mesh device owns each expander (block layout)."""
    return np.arange(n_expanders) // (n_expanders // n_devices)
