"""Sharded fabric driver tests (DESIGN.md §17).

  * shard_map replay — the per-shard replay is the SAME vmap composition
    as the single-device stacked replay, so per-expander counters and
    every pool leaf are BIT-identical to the vmap oracle (asserted at
    D=1 unconditionally; at D=2/D=4 when the session forced enough host
    devices — CI runs these under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``);
  * collective migration — the psum/ppermute collective apply replays
    the host planner's exact move sequence: spill parity vs the
    host-planned synchronous driver under pool invariants I1–I5;
  * in-jit planning — ``shard.plan_in_jit`` reproduces the host
    ``SpillPressure`` / ``TrafficRebalance`` plans (pages, srcs, dsts,
    urgency, move order) on scripted SegmentViews with clear margins
    (the rebalance time comparison is float32 in-jit vs float64 host —
    ties are scripted away, as documented in shard.py);
  * sync contract — one fused fetch per boundary (migration on), one
    deferred drain per replay() (migration off), and strictly fewer
    epoch host syncs than the PR 5 pipelined driver on the same trace;
  * per-device obs — ``Fabric.device_times`` reconciles with the
    Recorder-reconstructed per-device Perfetto track totals at
    rtol=1e-9, with recording changing no pool state.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import state as S
from repro.fabric import migration as MG
from repro.fabric import shard as FS
from repro.fabric.replay import Fabric
from repro.fabric.placement import WeightedInterleave
from helpers import check_pool_invariants
from test_fabric import POLICY, WINDOW, _saturating_fabric, _small_cfg, _trace

needs = lambda d: pytest.mark.skipif(
    jax.device_count() < d,
    reason=f"needs {d} XLA devices (force_host_device_count before jax init)")


def _saturating_pair(n_devices, **kw):
    """(sharded fabric, vmap synchronous reference) on the saturating
    spill fixture — same trace, same seed, independent state."""
    cfg, placement, fab, trace = _saturating_fabric()
    del fab
    rates = np.full((cfg.n_pages, cfg.blocks_per_page), 2, np.int32)

    def mk(**extra):
        return Fabric(cfg, POLICY, WeightedInterleave(2, cfg.n_pages,
                                                      [1.0, 0.0]),
                      seed=0, rates_table=jnp.asarray(rates), window=WINDOW,
                      spill=True, spill_interval=WINDOW, spill_k=8,
                      spill_low=40, **extra)

    return cfg, mk(shard_devices=n_devices, **kw), mk(sync_migration=True), \
        trace


def _skewed4_pair(n_devices):
    """(sharded fabric, vmap reference), both replayed with migration off:
    four expanders under a skewed placement on a real workload trace."""
    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=120, seed=1)

    def mk(**kw):
        return Fabric(cfg, POLICY,
                      WeightedInterleave(4, cfg.n_pages,
                                         [0.55, 0.15, 0.15, 0.15]),
                      seed=0, rates_table=jnp.asarray(rates), window=WINDOW,
                      spill=False, **kw)

    fab, ref = mk(shard_devices=n_devices), mk()
    fab.replay(ospn, wr, blk)
    ref.replay(ospn, wr, blk)
    return fab, ref


# ---------------------------------------------------------------------------
# shard_map replay bit-identity vs the vmap oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [
    1, pytest.param(2, marks=needs(2)), pytest.param(4, marks=needs(4))])
def test_shard_replay_bit_identical_to_vmap(n_devices):
    """Migration off: the shard_map-ed replay is bit-identical per
    expander to the vmap driver on a real workload trace — every pool
    leaf, counters included."""
    fab, ref = _skewed4_pair(n_devices)
    assert fab.state_identical(ref)
    assert fab.counters_by_expander() == ref.counters_by_expander()
    ss = fab.sync_stats()
    assert ss["drain_syncs"] == 1 and ss["boundary_syncs"] == 0
    # the deferred drain delivered per-segment telemetry identical to the
    # eager per-segment fetches
    assert len(fab.segment_deltas) == len(ref.segment_deltas)
    for a, b in zip(fab.segment_deltas, ref.segment_deltas):
        assert (a == b).all()


@pytest.mark.parametrize("n_devices", [
    1, pytest.param(2, marks=needs(2)), pytest.param(4, marks=needs(4))])
def test_sharded_delivered_time_is_mesh_invariant(n_devices):
    """The modeled time does not depend on the mesh: every expander's
    delivered seconds on a D-device mesh equal the vmap reference's
    exactly, so the modeled accesses/s is the same at every D."""
    fab, ref = _skewed4_pair(n_devices)
    assert (fab.delivered_time() == ref.delivered_time()).all()
    assert fab.bottleneck_time() == ref.bottleneck_time()


@pytest.mark.parametrize("n_devices", [
    1, pytest.param(2, marks=needs(2))])
def test_collective_spill_parity_and_invariants(n_devices):
    """Migration live: the in-jit planned + collectively applied spill
    epochs land bit-identically to the host-planned synchronous driver,
    with I1–I5 holding on every expander afterwards."""
    cfg, fab, ref, (ospn, wr, blk) = _saturating_pair(n_devices)
    fab.replay(ospn, wr, blk)
    ref.replay(ospn, wr, blk)
    assert ref.spill_stats()["events"] > 0, "fixture no longer saturates"
    assert fab.spill_stats()["events"] == ref.spill_stats()["events"]
    assert fab.state_identical(ref)
    for e in range(2):
        check_pool_invariants(S.pool_slice(fab.pools, e), cfg)
    ss = fab.sync_stats()
    assert ss["boundary_syncs"] == ss["boundaries"]
    assert ss["segment_syncs"] == 0 and ss["epoch_syncs"] == 0
    # strictly below the reference's segment+epoch sync count
    assert ss["host_syncs"] < ref.sync_stats()["host_syncs"]


def test_sharded_beats_pipelined_sync_count():
    """The acceptance comparison: epoch host-sync count on the sharded
    path is strictly below the PR 5 pipelined driver's on the same
    trace (one fused fetch per boundary vs one per segment + one per
    epoch)."""
    cfg, fab, _, (ospn, wr, blk) = _saturating_pair(1)
    _, _, _, _ = cfg, fab, None, None
    cfg2, placement, pipe, trace = _saturating_fabric()
    pipe.replay(*trace)
    assert pipe.epochs_applied > 0
    fab.replay(ospn, wr, blk)
    assert fab.sync_stats()["host_syncs"] < pipe.sync_stats()["host_syncs"]


# ---------------------------------------------------------------------------
# in-jit planner parity vs the host policies on scripted SegmentViews
# ---------------------------------------------------------------------------

def _view(free_units, free_singles, free_groups, eligible, referenced,
          delta, times, blocked=None, n_pages=32):
    n = len(free_units)
    return MG.SegmentView(
        free_units=np.asarray(free_units, np.int64),
        free_singles=np.asarray(free_singles, np.int64),
        free_groups=np.asarray(free_groups, np.int64),
        eligible=np.asarray(eligible, bool),
        referenced=np.asarray(referenced, bool),
        counters=np.zeros((n, S.NUM_COUNTERS), np.int64),
        delta=np.asarray(delta, np.int64),
        times=np.asarray(times, np.float64),
        recent=np.zeros((n_pages,), bool),
        blocked=np.zeros((n_pages,), bool) if blocked is None
        else np.asarray(blocked, bool))


def _jit_plan(policy, view):
    params = FS.plan_params(policy)
    pages, srcs, dsts, urgent = FS.plan_in_jit(
        params, jnp.asarray(view.free_units), jnp.asarray(view.free_singles),
        jnp.asarray(view.free_groups), jnp.asarray(view.eligible),
        jnp.asarray(view.referenced), jnp.asarray(view.delta),
        jnp.asarray(view.times, jnp.float32), jnp.asarray(view.blocked))
    pages = np.asarray(pages).reshape(-1)
    srcs = np.asarray(srcs).reshape(-1)
    dsts = np.asarray(dsts).reshape(-1)
    sel = pages >= 0
    if not sel.any():
        return None, bool(urgent)
    return MG.MigrationPlan(pages[sel].astype(np.int32),
                            srcs[sel].astype(np.int32),
                            dsts[sel].astype(np.int32)), bool(urgent)


def _assert_plans_equal(host_plan, jit_plan, jit_urgent):
    if host_plan is None:
        assert jit_plan is None
        assert not jit_urgent
        return
    assert jit_plan is not None
    assert (jit_plan.pages == host_plan.pages).all(), \
        (jit_plan.pages, host_plan.pages)
    assert (jit_plan.srcs == host_plan.srcs).all()
    assert (jit_plan.dsts == host_plan.dsts).all()
    assert jit_urgent == host_plan.urgent


def test_in_jit_spill_planner_matches_host():
    """Multi-source spill with donor decrements: two starved expanders,
    one urgent, conservative donor accounting making the donor
    ineligible for the second source — plan and order bit-equal."""
    n_pages = 32
    policy = MG.SpillPressure(k=3, low=16, proactive=1.5)
    eligible = np.zeros((4, n_pages), bool)
    eligible[0, [2, 5, 9, 11]] = True       # 4 candidates, k=3 clips
    eligible[1, [1, 30]] = True
    eligible[3, [7]] = True                 # starved but donor runs dry
    view = _view(
        free_units=[10, 20, 200, 23],       # e0 urgent (<low), e1/e3 proactive
        free_singles=[8, 8, 64, 8], free_groups=[2, 2, 16, 2],
        eligible=eligible, referenced=np.zeros_like(eligible),
        delta=np.zeros((4, S.NUM_COUNTERS)), times=[1.0, 1.0, 1.0, 1.0],
        n_pages=n_pages)
    host = policy.plan(view)
    assert host is not None and host.urgent     # sanity: scripted as intended
    assert len(host) > 3                        # multiple sources fired
    jit_plan, jit_urgent = _jit_plan(policy, view)
    _assert_plans_equal(host, jit_plan, jit_urgent)


def test_in_jit_spill_planner_respects_blocked_and_empty():
    policy = MG.SpillPressure(k=4, low=16, proactive=1.5)
    n_pages = 16
    eligible = np.zeros((2, n_pages), bool)
    eligible[0, [3, 4]] = True
    blocked = np.zeros((n_pages,), bool)
    blocked[[3, 4]] = True                      # livelock guard bars both
    view = _view(free_units=[10, 200], free_singles=[4, 32],
                 free_groups=[1, 8], eligible=eligible,
                 referenced=np.zeros_like(eligible),
                 delta=np.zeros((2, S.NUM_COUNTERS)), times=[1.0, 1.0],
                 blocked=blocked, n_pages=n_pages)
    host = policy.plan(view)
    jit_plan, jit_urgent = _jit_plan(policy, view)
    _assert_plans_equal(host, jit_plan, jit_urgent)
    assert jit_plan is None


def test_in_jit_rebalance_planner_matches_host():
    """Traffic trigger fires: hot expander 0 carries the host delta and a
    clear delivered-time lead; referenced-first candidate ordering and
    the pressure-claimed-page exclusion both exercised."""
    n_pages = 24
    policy = MG.TrafficRebalance(k=4, low=8, proactive=1.5,
                                 trigger=1.5, time_ratio=1.05)
    n = 3
    eligible = np.zeros((n, n_pages), bool)
    eligible[0, [1, 3, 5, 7, 9, 11]] = True
    referenced = np.zeros_like(eligible)
    referenced[0, [5, 9]] = True            # referenced move first
    delta = np.zeros((n, S.NUM_COUNTERS), np.int64)
    delta[0, S.C_HOST_RD] = 90              # hot: 90 of 100 accesses
    delta[1, S.C_HOST_RD] = 6
    delta[2, S.C_HOST_RD] = 4
    view = _view(free_units=[100, 60, 200], free_singles=[16, 16, 64],
                 free_groups=[4, 4, 16], eligible=eligible,
                 referenced=referenced, delta=delta,
                 times=[4.0, 1.5, 1.0], n_pages=n_pages)
    host = policy.plan(view)
    assert host is not None and len(host) == 4
    assert host.pages.tolist() == [5, 9, 1, 3]  # referenced first
    jit_plan, jit_urgent = _jit_plan(policy, view)
    _assert_plans_equal(host, jit_plan, jit_urgent)


def test_in_jit_rebalance_quiet_when_balanced():
    """No pressure, no traffic skew → both planners return nothing."""
    n_pages = 16
    policy = MG.TrafficRebalance(k=4, low=8)
    n = 2
    eligible = np.ones((n, n_pages), bool)
    delta = np.zeros((n, S.NUM_COUNTERS), np.int64)
    delta[:, S.C_HOST_RD] = 50              # perfectly balanced
    view = _view(free_units=[100, 100], free_singles=[16, 16],
                 free_groups=[4, 4], eligible=eligible,
                 referenced=np.zeros_like(eligible), delta=delta,
                 times=[1.0, 1.0], n_pages=n_pages)
    host = policy.plan(view)
    jit_plan, jit_urgent = _jit_plan(policy, view)
    _assert_plans_equal(host, jit_plan, jit_urgent)


def test_plan_params_rejects_host_only_policies():
    with pytest.raises(ValueError):
        FS.plan_params(MG.NoMigration())


# ---------------------------------------------------------------------------
# per-device observability (zero extra syncs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [
    1, pytest.param(2, marks=needs(2))])
def test_device_tracks_reconcile_device_times(n_devices):
    from repro.obs import Recorder
    from repro.obs import export as OBX
    cfg, fab_plain, ref, (ospn, wr, blk) = _saturating_pair(n_devices)
    del fab_plain
    rec = Recorder()
    rates = np.full((cfg.n_pages, cfg.blocks_per_page), 2, np.int32)
    fab = Fabric(cfg, POLICY, WeightedInterleave(2, cfg.n_pages, [1.0, 0.0]),
                 seed=0, rates_table=jnp.asarray(rates), window=WINDOW,
                 spill=True, spill_interval=WINDOW, spill_k=8, spill_low=40,
                 shard_devices=n_devices, obs=rec)
    fab.replay(ospn, wr, blk)
    ref.replay(ospn, wr, blk)
    assert fab.state_identical(ref), "recording changed sharded state"
    ss = fab.sync_stats()
    assert ss["boundary_syncs"] == ss["boundaries"]   # zero extra syncs
    dt = fab.device_times()
    tot = OBX.fabric_device_totals(rec)
    assert np.allclose(tot["device_s"], dt["device_s"], rtol=1e-9, atol=0)
    assert (tot["owners"] == dt["owners"]).all()
    # each device's extent bounds its owned expanders' delivered seconds
    per = np.asarray(fab.pipeline_times()["delivered_s"])
    for d in range(n_devices):
        assert dt["device_s"][d] >= per[dt["owners"] == d].max() - 1e-15
    trace = OBX.build_trace(rec)
    assert not OBX.validate_trace(trace)
    spans = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e.get("tid", 0) >= 1000]
    assert spans, "no per-device spans on a sharded run"
    for d in range(n_devices):
        ext = max(e["ts"] + e["dur"] for e in spans
                  if e["tid"] == 1000 + d) / 1e6
        assert np.isclose(ext, dt["device_s"][d], rtol=1e-9)


def test_vmap_runs_emit_no_device_tracks():
    from repro.obs import Recorder
    from repro.obs import export as OBX
    cfg, placement, fab, trace = _saturating_fabric()
    rec = Recorder()
    rates = np.full((cfg.n_pages, cfg.blocks_per_page), 2, np.int32)
    fab = Fabric(cfg, POLICY, WeightedInterleave(2, cfg.n_pages, [1.0, 0.0]),
                 seed=0, rates_table=jnp.asarray(rates), window=WINDOW,
                 spill=True, spill_interval=WINDOW, spill_k=8, spill_low=40,
                 obs=rec)
    fab.replay(*trace)
    assert fab.device_times() is None
    assert OBX.fabric_device_totals(rec) is None
    t = OBX.build_trace(rec)
    assert not any(e.get("tid", 0) >= 1000 for e in t["traceEvents"])


# ---------------------------------------------------------------------------
# mesh plumbing
# ---------------------------------------------------------------------------

def test_shard_devices_must_divide_expanders():
    cfg = _small_cfg()
    rates = np.zeros((cfg.n_pages, cfg.blocks_per_page), np.int32)
    with pytest.raises(ValueError):
        Fabric(cfg, POLICY, WeightedInterleave(3, cfg.n_pages,
                                               [0.5, 0.25, 0.25]),
               seed=0, rates_table=jnp.asarray(rates), shard_devices=2)


def test_device_of_expander_block_layout():
    assert FS.device_of_expander(8, 2).tolist() == [0, 0, 0, 0, 1, 1, 1, 1]
    assert FS.device_of_expander(4, 4).tolist() == [0, 1, 2, 3]
    assert FS.device_of_expander(4, 1).tolist() == [0, 0, 0, 0]
