"""Fabric segment scheduler: pipelined vmapped replay with overlapped
asynchronous migration (DESIGN.md §11/§13).

A merged (ospn, is_write, block) trace is partitioned by the placement's
current routing (base rule + migration overrides), padded per expander to
a common window-aligned length, and replayed through
``engine.batch._replay_windows_masked`` vmapped over the expander axis —
the window bodies are the single-pool ones, unchanged, so per-expander
counters are bit-identical to replaying that expander's partition through
``batch.replay_trace`` on a single pool (the fabric's parity contract,
asserted by tests/test_fabric.py).

The replay advances in *segments* (``spill_interval`` accesses per
expander, window-aligned). Each segment is one pipeline stage:

  stage A (device)  the segment's vmapped replay, which ALSO computes —
                    in-jit, no extra sync — the per-expander delivered
                    times, freelist headroom, page eligibility, and
                    referenced bits (``fabric.ops.segment_stats``);
  stage B (host)    while the next segment replays, the previous
                    segment's migration plan (a pluggable
                    ``fabric.migration.MigrationPolicy``) is computed
                    from those stats, applied as ONE jitted batch
                    (``fabric.ops.apply_migrations``), and its
                    override-table updates committed as ONE scatter
                    (``Placement.apply_epoch``).

Double-buffering (``pipeline_depth=2``, the default): the plan computed
off segment N's stats applies after segment N+1's replay — migration
cost is hidden behind foreground traffic, exactly the shadowed-promotion
argument at fabric scale. Accesses landing on a page whose plan is in
flight are masked to no-ops by the carried pending-migration mask
(``batch._replay_windows_masked``'s ``pending``) and replayed after the
epoch commits, routed to the page's final home. ``pipeline_depth=1``
degenerates to plan-and-apply at the same boundary and is bit-identical
to the synchronous reference driver (``sync_migration=True``, the PR 3
semantics: migration on the critical path) — the refactor's parity pin.

Host-sync contract (asserted by tests/test_fabric.py and test_obs.py,
mirroring serve's ``step_syncs == steps``): exactly ONE host sync per
replayed segment (the fused stats fetch) plus ONE per committed
migration epoch (the moved-pages fetch) — no per-page host writes, no
separate occupancy probe, no extra ``track_segments`` fetch.

Delivered time (DESIGN.md §12/§13): per-segment replay deltas and
per-epoch migration deltas are recorded host-side from the same fetches;
``Fabric.pipeline_times`` prices them through
``simx.time.pipeline_delivered_time`` — overlapped pricing
``max(replay, migration)`` per segment for the pipelined scheduler, the
``replay + migration`` sum for the synchronous reference.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import sharding as shd
from repro.common.contracts import sync_contract
from repro.common.types import PoolConfig
from repro.common.utils import next_pow2
from repro.core.engine import batch as B
from repro.core.engine import state as S
from repro.core.engine.policy import Policy
from repro.fabric import migration as MG
from repro.fabric import ops as fops
from repro.fabric import shard as FS
from repro.fabric.placement import Placement
from repro.simx import time as TM


def partition_trace(placement: Placement, ospns, writes, blocks,
                    window: int) -> Tuple[np.ndarray, ...]:
    """Route a trace and pack it per expander: [N, n_win, W] arrays plus a
    validity mask. Each expander's partition keeps the merged trace's
    relative order and sits as a prefix before the padding, so the masked
    replay walks full windows, then one partial window, then no-ops — the
    exact shapes ``batch.replay_trace`` produces on a single pool."""
    n = placement.n_expanders
    ospns = np.asarray(ospns, np.int32)
    writes = np.asarray(writes, bool)
    blocks = np.asarray(blocks, np.int32)
    eids = placement.route(ospns)
    counts = np.bincount(eids, minlength=n)
    n_win = next_pow2(-(-max(int(counts.max()), 1) // window))
    L = n_win * window
    o = np.zeros((n, L), np.int32)
    w = np.zeros((n, L), bool)
    b = np.zeros((n, L), np.int32)
    v = np.zeros((n, L), bool)
    for e in range(n):
        sel = eids == e
        k = int(counts[e])
        o[e, :k] = ospns[sel]
        w[e, :k] = writes[sel]
        b[e, :k] = blocks[sel]
        v[e, :k] = True
    shp = (n, n_win, window)
    return (o.reshape(shp), w.reshape(shp), b.reshape(shp), v.reshape(shp),
            eids)


@functools.partial(jax.jit, static_argnums=(1, 2, 9))
def _replay_stacked(pools: S.Pool, cfg: PoolConfig, policy: Policy,
                    ospns, writes, blocks, valid,
                    lanes: TM.DeviceLanes, pending, need_stats: bool):
    """Advance all expanders one segment AND compute everything the
    scheduler needs from it in-jit: per-expander delivered time (``lanes``
    is the stacked per-expander DeviceLanes pytree) and — when a
    migration policy will consume them — the migration stats (headroom /
    eligibility / referenced bits), one fused output, one host fetch, no
    dict round-trips. ``pending`` is the carried pending-migration page
    mask (bool[n_pages], shared across expanders); all-False reduces to
    the plain replay bit-for-bit. ``need_stats=False`` (migration off)
    skips the per-page stats so parity/scaling runs don't pay for facts
    no policy reads."""
    pools = jax.vmap(
        lambda p, o, w, b, v: B._replay_windows_masked(p, cfg, policy,
                                                       o, w, b, v, pending)
    )(pools, ospns, writes, blocks, valid)
    times = jax.vmap(TM.exec_time_vec)(pools.counters, lanes)
    stats = jax.vmap(lambda p: fops.segment_stats(p, cfg))(pools) \
        if need_stats else None
    return pools, times, stats


@functools.partial(jax.jit, static_argnums=(1,))
def _stacked_stats(pools: S.Pool, cfg: PoolConfig) -> fops.SegmentStats:
    """Post-apply migration facts for the whole stack (fetched with the
    epoch's moved pages in one sync — keeps the planner's view current)."""
    return jax.vmap(lambda p: fops.segment_stats(p, cfg))(pools)


class Fabric:
    """N expanders as one stacked pool state + placement + segment
    scheduler with pluggable migration.

    ``migration`` selects the ``fabric.migration.MigrationPolicy``:
    ``"spill"`` (freelist-pressure, default when ``spill=True``),
    ``"rebalance"`` (pressure + traffic-imbalance trigger fed by segment
    counter deltas and in-jit delivered times), ``"off"``, or a policy
    instance. ``spill_low`` is the compressed-region watermark in
    *chunks* (singles + 8x groups): an expander below it is starved; a
    donor must clear ``2 * spill_low``. ``spill_k`` pages move per
    (src, dst) pair per epoch. ``spill_interval`` is the segment length
    between migration decisions.

    ``pipeline_depth=2`` (default) overlaps: segment N's plan applies
    after segment N+1's replay, with in-flight pages' accesses deferred
    via the pending mask. ``pipeline_depth=1`` plans and applies at the
    same boundary. ``sync_migration=True`` forces the synchronous
    reference driver (PR 3 semantics, bit-identical to depth 1).

    ``obs`` is an optional ``repro.obs.Recorder``: when attached, the
    per-segment/per-epoch samples it accumulates are drained from the SAME
    single fetches the sync contract already budgets (DESIGN.md §16) —
    recording changes neither the sync counts nor one bit of pool state
    (with migration off, only the already-fused in-jit ``segment_stats``
    output is additionally computed, read-only over the pool).

    ``devices`` is the expander fleet's timing model: ``None`` (default
    ``DeviceConfig`` everywhere), one ``DeviceConfig`` (homogeneous), or
    a sequence — shorter sequences cycle, so ``[gen5, gen4]`` on N=4
    makes an alternating mixed-generation fleet. ``track_segments`` is
    accepted for PR 4 API compatibility but no longer changes behavior:
    per-segment counter deltas are ALWAYS recorded in ``segment_deltas``
    (the pipeline pricing needs them, and they fall out of the fused
    per-segment fetch at no extra sync — the flag used to buy an extra
    sync that no longer exists). ``on_epoch(fabric, plan, moved_pages)``
    is called after every committed migration epoch (tests hook
    invariant checks here)."""

    def __init__(self, cfg: PoolConfig, policy: Policy, placement: Placement,
                 *, seed: int = 0, rates_table=None, window: Optional[int] = None,
                 spill: bool = True, spill_interval: int = 2048,
                 spill_k: int = 16, spill_low: Optional[int] = None,
                 devices=None, track_segments: bool = False,
                 migration: Union[str, MG.MigrationPolicy, None] = None,
                 pipeline_depth: int = 2, sync_migration: bool = False,
                 shard_devices: Optional[int] = None,
                 on_epoch: Optional[Callable] = None, obs=None):
        if placement.n_pages != cfg.n_pages:
            raise ValueError("placement/page-space mismatch")
        if pipeline_depth not in (1, 2):
            raise ValueError("pipeline_depth must be 1 or 2")
        if shard_devices is not None and \
                placement.n_expanders % shard_devices:
            raise ValueError(f"{placement.n_expanders} expanders not "
                             f"divisible by shard_devices={shard_devices}")
        self.cfg = cfg
        self.policy = policy
        self.placement = placement
        self.n_expanders = placement.n_expanders
        self.window = B.DEFAULT_WINDOW if window is None else window
        self.spill_interval = spill_interval
        self.spill_k = spill_k
        self.spill_low = (max(16, cfg.n_cchunks // 16)
                          if spill_low is None else spill_low)
        if migration is None:
            migration = "spill" if spill else "off"
        if isinstance(migration, str):
            migration = MG.make_migration_policy(migration, k=spill_k,
                                                 low=self.spill_low)
        self.migration_policy = migration
        self.migration_enabled = (self.n_expanders > 1 and
                                  not isinstance(migration, MG.NoMigration))
        # back-compat alias only (the PR 3 name); the scheduler itself
        # reads migration_enabled
        self.spill_enabled = self.migration_enabled
        self.pipeline_depth = pipeline_depth
        self.sync_migration = sync_migration
        self.on_epoch = on_epoch
        self.obs = obs
        self.devices = TM.resolve_fleet(devices, self.n_expanders)
        self.lanes = TM.stack_devices(self.devices)
        self.pools = S.make_pool_stack(cfg, self.n_expanders, seed=seed,
                                       rates_table=rates_table)
        # sharded mode (DESIGN.md §17): the stacked pytree lives on a
        # device mesh, replayed shard_map-ed by the sharded driver with
        # synchronous migration scheduling collapsed into one jit dispatch
        # + one fetch per boundary
        self.shard_devices = shard_devices
        self.mesh = None
        if shard_devices is not None:
            self.mesh = shd.expander_mesh(shard_devices)
            if self.migration_enabled:
                FS.plan_params(self.migration_policy)  # fail fast: needs
                # an in-jit planner (spill / rebalance); custom host
                # policies must use the vmap drivers
            self.pools = FS.shard_pools(self.pools, self.mesh)
            self.lanes = FS.shard_pools(self.lanes, self.mesh)
        n = self.n_expanders
        self.spill_events = 0
        self.spill_pages_out = np.zeros((n,), np.int64)
        self.spill_pages_in = np.zeros((n,), np.int64)
        self.track_segments = track_segments
        # pipeline bookkeeping: per-segment replay counter deltas (int64
        # [N, NUM_COUNTERS] each) and per-epoch migration deltas, each
        # tagged (segment index whose replay it overlapped, delta,
        # genuinely-overlapped?) — urgent/sync/drain epochs carry False
        # and are priced on the critical path by pipeline_times
        self.segment_deltas: List[np.ndarray] = []
        self.migration_deltas: List[Tuple[int, np.ndarray, bool]] = []
        self.segments_replayed = 0
        self.segment_syncs = 0
        self.epochs_applied = 0
        self.epoch_syncs = 0
        self.spill_syncs = 0          # back-compat alias of epoch_syncs
        # sharded-driver sync bookkeeping: one fused fetch per boundary
        # (migration on), one deferred drain fetch per replay() call
        # (migration off — device refs accumulate, nothing blocks)
        self.boundaries = 0
        self.boundary_syncs = 0
        self.drain_syncs = 0
        self._deferred_refs: List[Tuple] = []
        self._last_counters = np.zeros((n, S.NUM_COUNTERS), np.int64)
        self._last_free: Optional[np.ndarray] = None
        self._pending_plan: Optional[MG.MigrationPlan] = None
        self._no_pending = jnp.zeros((cfg.n_pages,), bool)
        # livelock guard: pages whose last planned epoch moved NOTHING
        # (e.g. the donor's allocation guard refused every move) are
        # barred from re-planning until some epoch makes progress —
        # otherwise an un-appliable plan + its deferred accesses can
        # recur round after round with the trace never advancing
        self._blocked = np.zeros((cfg.n_pages,), bool)
        self._modeled_times = None
        if obs is not None:
            obs.attach_fabric(self)

    # -- pipeline stages -----------------------------------------------------

    def _dispatch_segment(self, o, w, b, v, sl,
                          pending_pages: Optional[np.ndarray]):
        """Stage A: dispatch one segment's vmapped replay (async). Returns
        the device-resident (times, stats, counters) of the post-replay
        state — fetched later in ONE sync."""
        if pending_pages is not None and len(pending_pages):
            pend = np.zeros((self.cfg.n_pages,), bool)
            pend[pending_pages] = True
            pend = jnp.asarray(pend)
        else:
            pend = self._no_pending
        self.pools, times, stats = _replay_stacked(
            self.pools, self.cfg, self.policy,
            jnp.asarray(o[:, sl]), jnp.asarray(w[:, sl]),
            jnp.asarray(b[:, sl]), jnp.asarray(v[:, sl]),
            self.lanes, pend,
            self.migration_enabled or self.obs is not None)
        self._modeled_times = times
        self.segments_replayed += 1
        return times, stats, self.pools.counters

    @sync_contract(syncs_per="segment", fetches=1)
    def _fetch_view(self, times, stats, counters,
                    recent: np.ndarray) -> Optional[MG.SegmentView]:
        """The ONE host sync per segment: fused fetch of delivered times,
        migration stats, and the counter snapshot; the replay delta falls
        out against the previous snapshot. With migration off the stats
        were never computed — ``None`` rides through the single fetch as
        an empty pytree, only the delta bookkeeping runs and no view is
        built (no policy would read it)."""
        stats, ctrs, t = jax.device_get((stats, counters, times))
        self.segment_syncs += 1
        ctrs = np.asarray(ctrs, np.int64)
        delta = ctrs - self._last_counters
        self._last_counters = ctrs
        self.segment_deltas.append(delta)
        if stats is not None:
            self._last_free = np.asarray(stats.free_units, np.int64)
        if self.obs is not None:
            # telemetry drain: the Recorder consumes the host values this
            # single contracted fetch already produced — zero extra syncs
            self.obs.record_segment(self.segments_replayed - 1, delta,
                                    np.asarray(t, np.float64),
                                    self._last_free)
        if stats is None:
            return None
        return MG.SegmentView(free_units=self._last_free,
                              free_singles=np.asarray(stats.free_singles,
                                                      np.int64),
                              free_groups=np.asarray(stats.free_groups,
                                                     np.int64),
                              eligible=np.asarray(stats.eligible),
                              referenced=np.asarray(stats.referenced),
                              counters=ctrs, delta=delta,
                              times=np.asarray(t, np.float64),
                              recent=recent, blocked=self._blocked.copy())

    def _plan(self, view: Optional[MG.SegmentView]
              ) -> Optional[MG.MigrationPlan]:
        """Ask the migration policy for an epoch, dropping pages the
        livelock guard barred (their last planned epoch moved nothing)."""
        if view is None:
            return None
        plan = self._plan_filtered(view)
        if plan is not None and self.obs is not None:
            self.obs.record_plan(self.segments_replayed - 1, plan,
                                 self.migration_policy.name)
        return plan

    def _plan_filtered(self, view: MG.SegmentView
                       ) -> Optional[MG.MigrationPlan]:
        plan = self.migration_policy.plan(view)
        if plan is None or not self._blocked.any():
            return plan
        keep = ~self._blocked[plan.pages]
        if keep.all():
            return plan
        if not keep.any():
            return None
        return MG.MigrationPlan(plan.pages[keep], plan.srcs[keep],
                                plan.dsts[keep], urgent=plan.urgent)

    def _dispatch_apply(self, plan: MG.MigrationPlan):
        """Stage B: dispatch one epoch's batched migration apply (async,
        sequenced after the in-flight segment's replay by data flow).
        Pages pad to a power of two so epochs compile a handful of
        shapes."""
        k = next_pow2(max(len(plan), 1))
        pages = np.full((k,), -1, np.int32)
        srcs = np.zeros((k,), np.int32)
        dsts = np.zeros((k,), np.int32)
        pages[:len(plan)] = plan.pages
        srcs[:len(plan)] = plan.srcs
        dsts[:len(plan)] = plan.dsts
        self.pools, moved = fops.apply_migrations(
            self.pools, self.cfg, self.policy,
            jnp.asarray(pages), jnp.asarray(srcs), jnp.asarray(dsts))
        return plan, srcs, dsts, moved

    @sync_contract(syncs_per="epoch", fetches=1)
    def _commit_epoch(self, plan: MG.MigrationPlan, srcs, dsts, moved,
                      overlapping_seg: int,
                      view: Optional[MG.SegmentView] = None,
                      overlapped: bool = False,
                      kind: str = "sync") -> np.ndarray:
        """Fetch the epoch's outcome (the ONE sync per epoch), commit the
        override-table updates as ONE batched scatter, and record the
        migration counter delta against the segment it overlapped.

        When the pipelined driver is about to plan at this same boundary,
        it passes the segment ``view`` and the commit REFRESHES its
        migration facts (headroom / eligibility / referenced) from the
        post-apply state — fetched in the same sync — so the planner never
        acts on pre-apply freelists (which over-spill and ping-pong).
        The replay delta and delivered times stay the segment's own.
        With no view to refresh (sync driver, urgent/depth-1 applies,
        drain) only the freelist tops ride along — no planner will read
        per-page facts, so none are computed."""
        if view is not None:
            extra = _stacked_stats(self.pools, self.cfg)
        else:
            # no planner will read per-page facts — only the freelist
            # tops ride along in the same single fetch
            extra = (self.pools.cfree.top, self.pools.gfree.top)
        moved, ctrs, extra = jax.device_get(
            (moved, self.pools.counters, extra))
        if view is not None:
            stats = extra
            free_units = np.asarray(stats.free_units, np.int64)
        else:
            stats = None
            ct, gt = extra
            free_units = (np.asarray(ct, np.int64) +
                          8 * np.asarray(gt, np.int64))
        self.epoch_syncs += 1
        self.spill_syncs = self.epoch_syncs
        ctrs = np.asarray(ctrs, np.int64)
        delta = ctrs - self._last_counters
        self.migration_deltas.append((overlapping_seg, delta, overlapped))
        self._last_counters = ctrs
        self._last_free = free_units
        moved = np.asarray(moved)
        sel = moved >= 0
        pages_moved = moved[sel].astype(np.int64)
        self.placement.apply_epoch(pages_moved, dsts[sel])
        self.epochs_applied += 1
        if len(pages_moved):
            np.add.at(self.spill_pages_out, srcs[sel], 1)
            np.add.at(self.spill_pages_in, dsts[sel], 1)
            pairs = {(int(s), int(d)) for s, d in zip(srcs[sel], dsts[sel])}
            self.spill_events += len(pairs)
            self._modeled_times = None    # migration traffic not yet priced
            self._blocked[:] = False      # progress: conditions changed
        else:
            # nothing moved: every move was refused at apply time. Bar the
            # plan's pages from re-planning until some epoch succeeds, or
            # an un-appliable plan recurs forever (livelock guard)
            self._blocked[plan.pages] = True
        if self.obs is not None:
            # telemetry drain: same single per-epoch fetch, zero extra syncs
            self.obs.record_epoch(overlapping_seg, delta, kind=kind,
                                  overlapped=overlapped, planned=len(plan),
                                  moved=len(pages_moved), urgent=plan.urgent,
                                  free_units=free_units)
        if view is not None:
            view.free_units = self._last_free
            view.free_singles = np.asarray(stats.free_singles, np.int64)
            view.free_groups = np.asarray(stats.free_groups, np.int64)
            view.eligible = np.asarray(stats.eligible)
            view.referenced = np.asarray(stats.referenced)
            view.recent[pages_moved] = True
            view.blocked = self._blocked.copy()
        if self.on_epoch is not None:
            self.on_epoch(self, plan, pages_moved)
        return pages_moved

    # -- drivers -------------------------------------------------------------

    def replay(self, ospns, writes, blocks) -> "Fabric":
        """Replay a merged trace through all expanders.

        The trace is partitioned ONCE and replayed in window-aligned
        segments of ``spill_interval`` accesses per expander, so each
        expander's window boundaries are exactly those of
        ``batch.replay_trace`` over its partition — with no migration,
        per-expander counters are bit-identical to single-pool replays of
        the partitions (the parity contract). When a migration epoch
        commits, the unconsumed tails (plus any accesses deferred by the
        pending mask) re-merge in original trace order and re-partition,
        so accesses follow migrated pages to their new expander."""
        rem = (np.asarray(ospns, np.int32), np.asarray(writes, bool),
               np.asarray(blocks, np.int32))
        if self.shard_devices is not None:
            driver = self._replay_sharded
        elif self.sync_migration:
            driver = self._replay_sync
        else:
            driver = self._replay_pipelined
        while rem is not None and len(rem[0]):
            rem = driver(rem)
        if self._deferred_refs:
            # sharded migration-off: nothing forced a fetch mid-run; the
            # per-segment bookkeeping drains in ONE deferred sync now
            self._drain_deferred()
        if self._pending_plan is not None:
            # drain: the plan computed off the final segment's stats has
            # nothing left to overlap — apply and commit it now (the
            # synchronous path would have applied it at the same boundary)
            applied = self._dispatch_apply(self._pending_plan)
            self._pending_plan = None
            self._commit_epoch(*applied, self.segments_replayed,
                               kind="drain")
        return self

    def _segments(self, n_win: int) -> int:
        if not self.migration_enabled:
            return n_win
        seg = next_pow2(max(self.spill_interval // self.window, 1))
        return min(seg, n_win)

    def _rebuild(self, cur, pos_by_exp, hi: int, deferred: np.ndarray):
        """Re-merge the unconsumed per-expander tails (plus deferred
        accesses) in original merged-trace order for re-partitioning —
        after re-routing, one expander may merge accesses from several
        old streams, and sorting by trace position keeps its replay order
        faithful."""
        done = hi * self.window
        tails = [p[done:] for p in pos_by_exp]
        pos = np.sort(np.concatenate([deferred.astype(np.int64)] +
                                     [t.astype(np.int64) for t in tails]))
        if not len(pos):
            return None
        return tuple(a[pos] for a in cur)

    def _replay_pipelined(self, cur):
        """One partition round of the double-buffered scheduler. Returns
        the re-merged remainder when an epoch commit re-routes pages (or
        deferred accesses must replay), ``None`` when the round consumed
        everything."""
        o, w, b, v, eids = partition_trace(self.placement, *cur, self.window)
        n = self.n_expanders
        n_win = o.shape[1]
        seg = self._segments(n_win)
        pos_by_exp = [np.nonzero(eids == e)[0] for e in range(n)]
        none = np.empty((0,), np.int64)
        for lo in range(0, n_win, seg):
            hi = min(lo + seg, n_win)
            in_flight, self._pending_plan = self._pending_plan, None
            times, stats, ctrs = self._dispatch_segment(
                o, w, b, v, slice(lo, hi),
                in_flight.pages if in_flight is not None else None)
            applied = None
            if in_flight is not None:
                # overlap: the previous segment's plan applies behind this
                # segment's replay, one jit call, overrides batched below
                applied = self._dispatch_apply(in_flight)
            view = self._fetch_view(times, stats, ctrs,
                                    np.zeros((self.cfg.n_pages,), bool))
            moved_pages, deferred = none, none
            if applied is not None:
                moved_pages = self._commit_epoch(
                    *applied, self.segments_replayed - 1, view,
                    overlapped=True, kind="overlapped")
                # accesses this segment deferred by the pending mask —
                # replayed after the commit, routed to the final home
                defer = []
                for e in range(n):
                    seg_pos = pos_by_exp[e][lo * self.window:
                                            hi * self.window]
                    dsel = np.isin(cur[0][seg_pos], in_flight.pages)
                    defer.append(seg_pos[dsel])
                deferred = np.concatenate(defer) if defer else none
            if self.migration_enabled:
                plan = self._plan(view)
                if plan is not None and (self.pipeline_depth == 1 or
                                         plan.urgent):
                    # apply at the same boundary: depth-1 degenerates to
                    # the synchronous reference driver bit-for-bit, and
                    # an URGENT plan (source already below the hard
                    # watermark) must not wait a segment — relief that
                    # lands after the freelists run dry is corruption,
                    # not overlap
                    m1 = self._commit_epoch(
                        *self._dispatch_apply(plan),
                        self.segments_replayed - 1,
                        kind="urgent" if plan.urgent else "sync")
                    moved_pages = np.concatenate([moved_pages, m1])
                elif plan is not None:
                    self._pending_plan = plan
            if len(moved_pages) or len(deferred):
                rem = self._rebuild(cur, pos_by_exp, hi, deferred)
                if rem is not None:
                    return rem
        return None

    def _replay_sync(self, cur):
        """The synchronous reference driver (PR 3 semantics): plan and
        apply at every segment boundary, migration cost on the critical
        path, no pending mask, no deferral. Kept as the parity anchor the
        depth-1 pipeline is pinned against (tests/test_fabric.py)."""
        o, w, b, v, eids = partition_trace(self.placement, *cur, self.window)
        n = self.n_expanders
        n_win = o.shape[1]
        seg = self._segments(n_win)
        pos_by_exp = [np.nonzero(eids == e)[0] for e in range(n)]
        for lo in range(0, n_win, seg):
            hi = min(lo + seg, n_win)
            times, stats, ctrs = self._dispatch_segment(
                o, w, b, v, slice(lo, hi), None)
            view = self._fetch_view(times, stats, ctrs,
                                    np.zeros((self.cfg.n_pages,), bool))
            if not self.migration_enabled:
                continue
            plan = self._plan(view)
            if plan is None:
                continue
            moved = self._commit_epoch(*self._dispatch_apply(plan),
                                       self.segments_replayed - 1)
            if len(moved):
                rem = self._rebuild(cur, pos_by_exp, hi,
                                    np.empty((0,), np.int64))
                if rem is not None:
                    return rem
        return None

    def _replay_sharded(self, cur):
        """The sharded driver (DESIGN.md §17): each segment boundary is
        ONE jit dispatch of the shard_map-ed replay + in-jit plan +
        collective apply (``fabric.shard.boundary_step``), committed with
        ONE fused fetch (``_commit_boundary``) — synchronous migration
        scheduling (the ``_replay_sync`` semantics, bit-identical for the
        integer ``spill`` planner) at one host sync per boundary instead
        of the pipelined driver's one per segment plus one per epoch.
        With migration off nothing is fetched at all: per-segment device
        references accumulate and drain in one deferred sync at the end
        of ``replay()``."""
        o, w, b, v, eids = partition_trace(self.placement, *cur, self.window)
        n = self.n_expanders
        n_win = o.shape[1]
        seg = self._segments(n_win)
        pos_by_exp = [np.nonzero(eids == e)[0] for e in range(n)]
        for lo in range(0, n_win, seg):
            hi = min(lo + seg, n_win)
            sl = slice(lo, hi)
            args = (self.pools, jnp.asarray(o[:, sl]), jnp.asarray(w[:, sl]),
                    jnp.asarray(b[:, sl]), jnp.asarray(v[:, sl]),
                    self.lanes, self._no_pending)
            if not self.migration_enabled:
                step = FS.replay_step(self.mesh, self.cfg, self.policy,
                                      self.obs is not None)
                outs = step(*args)
                self.pools, times = outs[0], outs[1]
                self._modeled_times = times
                self.segments_replayed += 1
                self._deferred_refs.append(
                    (times, self.pools.counters,
                     outs[2] if len(outs) > 2 else None))
                continue
            step = FS.boundary_step(self.mesh, self.cfg, self.policy,
                                    FS.plan_params(self.migration_policy),
                                    self.n_expanders)
            (self.pools, times, ctrs_mid, free_pre, fc, fg,
             pages, srcs, dsts, urgent, moved) = step(
                *args, jnp.asarray(self._blocked))
            self._modeled_times = times
            self.segments_replayed += 1
            self.boundaries += 1
            moved_pages = self._commit_boundary(
                times, ctrs_mid, free_pre, fc, fg, pages, srcs, dsts,
                urgent, moved)
            if len(moved_pages):
                rem = self._rebuild(cur, pos_by_exp, hi,
                                    np.empty((0,), np.int64))
                if rem is not None:
                    return rem
        return None

    @sync_contract(syncs_per="boundary", fetches=1)
    def _commit_boundary(self, times, ctrs_mid, free_pre, fc, fg,
                         pages, srcs, dsts, urgent, moved) -> np.ndarray:
        """The sharded driver's ONE host sync per segment boundary: fetch
        the boundary dispatch's whole outcome — post-replay times and
        counters (the segment's replay delta), the in-jit plan, the
        applied moves, and the post-apply counters/freelists (the epoch's
        migration delta) — in a single fused ``device_get``, then run the
        same host bookkeeping ``_fetch_view`` + ``_commit_epoch`` split
        across two syncs on the vmap drivers."""
        (t, ctrs_mid, free_pre, fc, fg, pages, srcs, dsts, urgent, moved,
         ctrs_post) = jax.device_get(
            (times, ctrs_mid, free_pre, fc, fg, pages, srcs, dsts,
             urgent, moved, self.pools.counters))
        self.boundary_syncs += 1
        ctrs_mid = np.asarray(ctrs_mid, np.int64)
        delta_replay = ctrs_mid - self._last_counters
        self.segment_deltas.append(delta_replay)
        self._last_free = np.asarray(free_pre, np.int64)
        if self.obs is not None:
            # telemetry drain: host values from this single fused fetch
            self.obs.record_segment(self.segments_replayed - 1,
                                    delta_replay, np.asarray(t, np.float64),
                                    self._last_free)
        pages = np.asarray(pages).reshape(-1)
        srcs = np.asarray(srcs).reshape(-1)
        dsts = np.asarray(dsts).reshape(-1)
        psel = pages >= 0
        if not psel.any():
            # empty plan: no epoch happened (the collective apply was a
            # bit-exact no-op); the snapshot advances to post-replay
            self._last_counters = ctrs_mid
            return np.empty((0,), np.int64)
        plan = MG.MigrationPlan(pages[psel].astype(np.int32),
                                srcs[psel].astype(np.int32),
                                dsts[psel].astype(np.int32),
                                urgent=bool(urgent))
        if self.obs is not None:
            self.obs.record_plan(self.segments_replayed - 1, plan,
                                 self.migration_policy.name)
        ctrs_post = np.asarray(ctrs_post, np.int64)
        delta_mig = ctrs_post - ctrs_mid
        self.migration_deltas.append(
            (self.segments_replayed - 1, delta_mig, False))
        self._last_counters = ctrs_post
        free_units = np.asarray(fc, np.int64) + 8 * np.asarray(fg, np.int64)
        self._last_free = free_units
        moved = np.asarray(moved)
        msel = moved >= 0
        pages_moved = moved[msel].astype(np.int64)
        self.placement.apply_epoch(pages_moved, dsts[msel])
        self.epochs_applied += 1
        if len(pages_moved):
            np.add.at(self.spill_pages_out, srcs[msel], 1)
            np.add.at(self.spill_pages_in, dsts[msel], 1)
            pairs = {(int(s), int(d)) for s, d in zip(srcs[msel],
                                                      dsts[msel])}
            self.spill_events += len(pairs)
            self._modeled_times = None    # migration traffic not yet priced
            self._blocked[:] = False      # progress: conditions changed
        else:
            self._blocked[plan.pages] = True
        if self.obs is not None:
            self.obs.record_epoch(self.segments_replayed - 1, delta_mig,
                                  kind="sync", overlapped=False,
                                  planned=len(plan), moved=len(pages_moved),
                                  urgent=plan.urgent, free_units=free_units)
        if self.on_epoch is not None:
            self.on_epoch(self, plan, pages_moved)
        return pages_moved

    @sync_contract(syncs_per="drain", fetches=1)
    def _drain_deferred(self) -> None:
        """Drain the sharded migration-off driver's accumulated device
        references — per-segment times, counter snapshots, and (with obs
        attached) freelist headroom — in ONE deferred fetch per
        ``replay()`` call, after the whole trace replayed. Nothing
        host-side depended on any of it mid-run, so the per-segment sync
        of the vmap drivers goes to zero."""
        fetched = jax.device_get(self._deferred_refs)
        self.drain_syncs += 1
        self._deferred_refs = []
        seg0 = self.segments_replayed - len(fetched)
        for i, (t, ctrs, free) in enumerate(fetched):
            ctrs64 = np.asarray(ctrs, np.int64)
            delta = ctrs64 - self._last_counters
            self._last_counters = ctrs64
            self.segment_deltas.append(delta)
            if free is not None:
                self._last_free = np.asarray(free, np.int64)
            if self.obs is not None:
                self.obs.record_segment(seg0 + i, delta,
                                        np.asarray(t, np.float64),
                                        self._last_free
                                        if free is not None else None)

    # -- metrics -------------------------------------------------------------

    def counters(self) -> Dict[str, int]:
        """Summed traffic counters across expanders."""
        return S.stacked_counters_dict(self.pools)

    @sync_contract(syncs_per="call", fetches=1)
    def delivered_time(self, exact: bool = True) -> np.ndarray:
        """Per-expander delivered seconds for the traffic replayed so far,
        each priced by that expander's own ``DeviceConfig`` — migration
        traffic included on the expander where it physically occurred
        (the source's demotion-reads, the donor's writes + compression
        stores land in those pools' counters).

        ``exact=True`` (default, host-side) recomputes in float64 through
        the same ``exec_time_vec`` — the parity-grade numbers the tests
        pin. ``exact=False`` returns the float32 values the vmapped
        replay computed on device (zero extra device work) — or, when a
        trailing migration invalidated them, re-prices the current
        counters through the same float32 device path, never the float64
        one (the float32-vs-float64 parity asserts stay meaningful).

        Both flavors cost exactly ONE fused fetch (the declared
        contract), so calling it mid-run composes with the schedulers'
        sync budgets instead of quietly doubling them."""
        times = self._modeled_times
        if times is None:
            times = TM.exec_time_vec(self.pools.counters, self.lanes)
        times, counters = jax.device_get((times, self.pools.counters))
        if not exact:
            return np.asarray(times, np.float64)
        return TM.exec_time_vec(np.asarray(counters, np.float64),
                                TM.stack_devices(self.devices, xp=np))

    def bottleneck_time(self, exact: bool = True) -> float:
        """Delivered time of the fabric serving one merged trace: expanders
        run in parallel, so the bottleneck expander governs."""
        return float(np.max(self.delivered_time(exact=exact)))

    def pipeline_times(self) -> Optional[Dict[str, object]]:
        """Pipeline-model delivered seconds from the recorded per-segment
        replay deltas + per-epoch migration deltas (DESIGN.md §13):
        ``overlapped_s`` prices each segment as max(replay, migration)
        (the double-buffered scheduler), ``sync_s`` as their sum (the
        synchronous reference). Both are per-expander float64 arrays over
        the SAME deltas, so overlapped <= sync holds by construction;
        ``delivered_s`` picks the pricing matching how this fabric
        actually ran. Epochs that did NOT physically overlap a segment's
        replay — urgent emergency spills, depth-1/synchronous applies,
        and drain epochs — get their own zero-replay rows, so both
        pricings charge them in full on the critical path; only epochs
        the scheduler genuinely hid behind a foreground segment are
        eligible for the max() discount."""
        rows = self._pipeline_rows()
        if rows is None:
            return None
        replay, mig = rows
        lanes = TM.stack_devices(self.devices, xp=np)
        over = TM.pipeline_delivered_time(replay, mig, lanes, overlapped=True)
        sync = TM.pipeline_delivered_time(replay, mig, lanes,
                                          overlapped=False)
        overlapped_run = (not self.sync_migration and
                          self.pipeline_depth > 1 and
                          self.shard_devices is None)
        return {"overlapped_s": over, "sync_s": sync,
                "mode": "overlapped" if overlapped_run else "sync",
                "delivered_s": over if overlapped_run else sync}

    def _pipeline_rows(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """(replay [R,N,C], mig [R,N,C]) — the pipeline row matrices
        shared by ``pipeline_times`` and ``device_times`` (and rebuilt
        independently by obs/export.py from the Recorder's samples; the
        rtol=1e-9 track reconciliation pins the two constructions)."""
        if not self.segment_deltas:
            return None
        n, c = self.n_expanders, S.NUM_COUNTERS
        n_seg = len(self.segment_deltas)
        sync_epochs = [d for _, d, over in self.migration_deltas
                       if not over]
        rows = n_seg + len(sync_epochs)
        replay = np.zeros((rows, n, c), np.float64)
        replay[:n_seg] = np.stack(self.segment_deltas)
        mig = np.zeros_like(replay)
        for i, d, over in self.migration_deltas:
            if over:
                mig[min(i, n_seg - 1)] += d
        for j, d in enumerate(sync_epochs):
            mig[n_seg + j] += d
        return replay, mig

    def device_times(self) -> Optional[Dict[str, object]]:
        """Per-XLA-device delivered seconds on the sharded driver: the
        expanders a device owns execute inside one jit dispatch, so the
        device finishes pipeline row ``r`` when its slowest owned
        expander does — ``device_s[d] = sum_r max_{e in d} max(replay,
        mig)``. Built from the SAME ``_pipeline_rows`` matrices as
        ``pipeline_times`` (on the sharded driver every epoch is a
        zero-replay sync row, so the per-row max degenerates to the sync
        pricing and ``device_s[d] >= max_{e in d} delivered_s[e]``).
        None on vmap drivers or before any segment has replayed."""
        if self.shard_devices is None:
            return None
        rows = self._pipeline_rows()
        if rows is None:
            return None
        replay, mig = rows
        lanes = TM.stack_devices(self.devices, xp=np)
        cell = np.maximum(np.atleast_2d(TM.exec_time_vec(replay, lanes,
                                                         xp=np)),
                          np.atleast_2d(TM.exec_time_vec(mig, lanes,
                                                         xp=np)))
        owners = FS.device_of_expander(self.n_expanders, self.shard_devices)
        device_s = np.asarray([cell[:, owners == d].max(axis=1).sum()
                               for d in range(self.shard_devices)],
                              np.float64)
        return {"device_s": device_s, "owners": owners}

    def park_capacity(self) -> np.ndarray:
        """Per-expander compressed-region headroom in chunk units, straight
        from the last replayed segment's in-jit stats (no host sync when a
        segment has run) — the hook per-expander park-capacity limits for
        fabric-aware serving build on (ROADMAP)."""
        if self._last_free is None:
            ct, gt = jax.device_get((self.pools.cfree.top,
                                     self.pools.gfree.top))
            return np.asarray(ct, np.int64) + 8 * np.asarray(gt, np.int64)
        return self._last_free

    def state_identical(self, other: "Fabric") -> bool:
        """Bit-identity of two fabrics' end states: every leaf of the
        stacked pool pytree (so counters included), plus the placement
        override tables. THE parity predicate — the depth-1-vs-sync pin
        in tests, the launcher and the CI smoke all call this one
        definition."""
        pools_equal = jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.array_equal(a, b)), self.pools,
            other.pools))
        return bool(pools_equal and
                    (self.placement.overrides ==
                     other.placement.overrides).all())

    def counters_by_expander(self) -> List[Dict[str, int]]:
        return S.per_expander_counters(self.pools)

    def spill_stats(self) -> Dict[str, object]:
        return {
            "events": self.spill_events,
            "pages_out": self.spill_pages_out.tolist(),
            "pages_in": self.spill_pages_in.tolist(),
            "syncs": self.epoch_syncs,
        }

    def sync_stats(self) -> Dict[str, int]:
        """The host-sync contract (asserted by tests/test_fabric.py):
        on the vmap drivers one fused stats fetch per replayed segment
        plus one moved-pages fetch per committed migration epoch; on the
        sharded driver one fused fetch per boundary (migration on) or
        one deferred drain per ``replay()`` call (migration off) —
        nothing else."""
        return {
            "segments": self.segments_replayed,
            "segment_syncs": self.segment_syncs,
            "epochs": self.epochs_applied,
            "epoch_syncs": self.epoch_syncs,
            "boundaries": self.boundaries,
            "boundary_syncs": self.boundary_syncs,
            "drain_syncs": self.drain_syncs,
            "host_syncs": self.segment_syncs + self.epoch_syncs +
            self.boundary_syncs + self.drain_syncs,
        }
