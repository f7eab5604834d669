"""Multi-expander fabric tests (DESIGN.md §11/§13).

  * parity — the vmapped masked replay adds ZERO counter drift: per-expander
    counters are bit-identical to single-pool ``batch.replay_trace`` runs of
    each partition (and the N=1 fabric is bit-identical to a plain
    single-pool replay of the merged trace);
  * spill — a skew-saturated expander (cfree/gfree draining) spills to an
    idle donor: invariants I1–I5 hold on every expander afterwards and
    traffic lands on the right expander's counters;
  * segment scheduler — the depth-1 pipeline is bit-identical to the
    synchronous reference driver; overlapped (depth-2) migration defers
    in-flight pages' accesses to the page's final home; the rebalance
    policy shrinks the per-expander delivered-time spread on a skewed
    trace while I1–I5 hold after every epoch; pipeline pricing satisfies
    overlapped <= sync on the same deltas; one host sync per segment and
    one per epoch;
  * serving — lanes stripe across expanders, parked payloads are charged
    per-expander and victim selection balances parked load.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import replace
from repro.core.engine import batch as B
from repro.core.engine import state as S
from repro.core.engine.policy import POLICIES, SecondChanceLanes
from repro.fabric import (CapacityAware, Fabric, LocalityAffinity,
                          StaticInterleave, WeightedInterleave)
from repro.fabric import migration as MG
from repro.fabric import ops as fops
from repro.simx.engine import pool_cfg_for
from repro.simx.trace import WORKLOADS, make_rates_table, make_trace
from helpers import check_pool_invariants

POLICY = POLICIES["ibex"]
WINDOW = 8   # small windows keep the masked-path compiles test-sized


def _small_cfg(prom=16, n_pages=64, n_cchunks=None):
    return pool_cfg_for(POLICY, n_pages=n_pages, n_pchunks=prom,
                        n_cchunks=n_cchunks or 2 * n_pages * 8)


def _trace(cfg, n_accesses, seed=0, wl="mcf"):
    spec = WORKLOADS[wl]
    rates = make_rates_table(spec, cfg.n_pages, seed=seed)
    ospn, wr, blk = make_trace(spec, n_accesses=n_accesses,
                               n_pages=cfg.n_pages, seed=seed)
    return rates, ospn, wr, blk


def test_single_expander_fabric_matches_single_pool_exact():
    """N=1 fabric == plain ``replay_trace`` of the merged trace, counter for
    counter (the masked window path reuses the single-pool bodies)."""
    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=90)
    fab = Fabric(cfg, POLICY, StaticInterleave(1, cfg.n_pages), seed=0,
                 rates_table=jnp.asarray(rates), window=WINDOW, spill=False)
    fab.replay(ospn, wr, blk)
    pool = S.pool_slice(S.make_pool_stack(cfg, 1, seed=0,
                                          rates_table=jnp.asarray(rates)), 0)
    pool = B.replay_trace(pool, cfg, POLICY, ospn, wr, blk, window=WINDOW)
    assert fab.counters() == S.counters_dict(pool)


@pytest.mark.parametrize("placement_cls", [StaticInterleave, LocalityAffinity,
                                           CapacityAware])
def test_fabric_counter_sum_parity_per_shard_exact(placement_cls):
    """Summed fabric counters == sum of single-pool replays of the same
    merged trace's per-expander partitions, exactly, for every placement
    mode — and each expander's own counters match its shard's replay."""
    n_exp = 3
    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=120, seed=1)
    placement = placement_cls(n_exp, cfg.n_pages)
    fab = Fabric(cfg, POLICY, placement, seed=0,
                 rates_table=jnp.asarray(rates), window=WINDOW, spill=False)
    fab.replay(ospn, wr, blk)
    # reference: each shard through the canonical single-pool front-end,
    # from the identical starting state (same derived RNG stream)
    eids = placement.route(ospn)
    stack0 = S.make_pool_stack(cfg, n_exp, seed=0,
                               rates_table=jnp.asarray(rates))
    total = {k: 0 for k in S.COUNTER_NAMES}
    for e in range(n_exp):
        sel = eids == e
        ref = B.replay_trace(S.pool_slice(stack0, e), cfg, POLICY,
                             ospn[sel], wr[sel], blk[sel], window=WINDOW)
        ce = S.counters_dict(ref)
        assert fab.counters_by_expander()[e] == ce, f"expander {e} drifted"
        for k, v in ce.items():
            total[k] += v
    assert fab.counters() == total
    # invariants hold on every expander
    for e in range(n_exp):
        check_pool_invariants(S.pool_slice(fab.pools, e), cfg)


def test_fabric_vs_merged_single_pool_within_tolerance():
    """An N-expander fabric vs ONE pool with N× the physical regions (and
    N× the metadata cache), replaying the same merged trace: host access
    counts match exactly (they are per-access), total internal traffic
    agrees within the documented tolerance — the shared-vs-sharded metadata
    cache and per-expander demotion cadence shift counters, they do not
    change the traffic story (DESIGN.md §11)."""
    n_exp = 2
    cfg = _small_cfg(prom=16, n_pages=64)
    rates, ospn, wr, blk = _trace(cfg, n_accesses=512, seed=2)
    fab = Fabric(cfg, POLICY, StaticInterleave(n_exp, cfg.n_pages), seed=0,
                 rates_table=jnp.asarray(rates), window=WINDOW, spill=False)
    fab.replay(ospn, wr, blk)
    big = replace(cfg, n_pchunks=cfg.n_pchunks * n_exp,
                  n_cchunks=cfg.n_cchunks * n_exp,
                  mcache_sets=cfg.mcache_sets * n_exp)
    pool = S.make_pool(big, seed=0, rates_table=jnp.asarray(rates))
    pool = B.replay_trace(pool, big, POLICY, ospn, wr, blk, window=WINDOW)
    cf, cs = fab.counters(), S.counters_dict(pool)
    assert cf["host_reads"] == cs["host_reads"]
    assert cf["host_writes"] == cs["host_writes"]
    from repro.simx.engine import TRAFFIC_KEYS
    tf = sum(cf[k] for k in TRAFFIC_KEYS)
    ts = sum(cs[k] for k in TRAFFIC_KEYS)
    assert abs(tf - ts) / max(ts, 1) < 0.35, (tf, ts)


def _saturating_fabric(n_pages=96, n_used=40, **kw):
    """A fabric rigged to exhaust expander 0's compressed region: every
    page placed on expander 0 (WeightedInterleave [1, 0]); every page
    8-bit-compressible (4 single chunks, no aligned groups), so first-touch
    writes + watermark demotions demand ~160 chunks against 80 singles —
    the spill path must carry the overflow to the idle expander 1. (prom
    must be >= the clock engine's 16-entry fetch group; spill cadence is
    one window so within-segment demand never outruns the watermark.)
    ``kw`` passes on to ``Fabric`` (e.g. a mixed ``devices`` fleet)."""
    cfg = _small_cfg(prom=16, n_pages=n_pages, n_cchunks=96)
    rates = np.full((n_pages, cfg.blocks_per_page), 2, np.int32)
    placement = WeightedInterleave(2, n_pages, [1.0, 0.0])
    fab = Fabric(cfg, POLICY, placement, seed=0,
                 rates_table=jnp.asarray(rates), window=WINDOW,
                 spill=True, spill_interval=WINDOW, spill_k=8, spill_low=40,
                 **kw)
    # one first-touch write per used page (single lap: the donor sees no
    # host access unless overrides redirect a later lap)
    ospn = np.arange(n_used, dtype=np.int32)
    wr = np.ones((n_used,), bool)
    blk = np.zeros((n_used,), np.int32)
    return cfg, placement, fab, (ospn, wr, blk)


def test_skewed_saturation_spills_and_keeps_invariants():
    """Freelist exhaustion under skewed placement: expander 0 saturates
    while expander 1 idles. The spill path must fire, move pages to the
    donor, keep I1–I5 on BOTH expanders, and charge migration traffic where
    it physically happens: demotion-reads on the starved source,
    demotion-writes + compression-store bookkeeping on the donor — which
    sees no host accesses at all."""
    cfg, placement, fab, (ospn, wr, blk) = _saturating_fabric()
    fab.replay(ospn, wr, blk)

    stats = fab.spill_stats()
    assert stats["events"] > 0, "spill never fired"
    assert stats["pages_out"][0] > 0 and stats["pages_in"][1] > 0
    assert (placement.overrides >= 0).sum() == stats["pages_out"][0]
    for e in range(2):
        check_pool_invariants(S.pool_slice(fab.pools, e), cfg)
    c0, c1 = fab.counters_by_expander()
    # all host traffic on expander 0; the donor has zero host accesses
    assert c0["host_writes"] == int(wr.sum()) and c1["host_writes"] == 0
    assert c1["host_reads"] == 0
    # migration charged on the right sides
    assert c0["demo_rd"] > 0, "source not charged for spill reads"
    assert c1["demo_wr"] > 0, "donor not charged for spill writes"
    assert c1["promotions"] == 0 == c1["demotions_dirty"]


def test_spilled_page_follows_to_donor():
    """After a spill, accesses to a migrated page are routed (and charged)
    to the donor expander — the placement override re-routes mid-trace."""
    cfg, placement, fab, (ospn, wr, blk) = _saturating_fabric()
    fab.replay(ospn, wr, blk)
    assert fab.spill_stats()["events"] > 0
    moved = np.nonzero(placement.overrides >= 0)[0]
    assert len(moved) > 0
    # read a migrated page: the donor serves (and is charged for) it
    tail = np.full((WINDOW,), moved[0], np.int32)
    before = fab.counters_by_expander()[1]["host_reads"]
    fab.replay(tail, np.zeros((WINDOW,), bool), np.zeros((WINDOW,), np.int32))
    after = fab.counters_by_expander()[1]["host_reads"]
    assert after - before == WINDOW
    for e in range(2):
        check_pool_invariants(S.pool_slice(fab.pools, e), cfg)


def test_delivered_time_mixed_fleet_per_expander_devices():
    """Delivered-time accounting (DESIGN.md §12) through the fabric: a
    mixed-generation fleet prices each expander's counters through its OWN
    DeviceConfig inside the vmapped replay. The in-jit float32 values match
    the host float64 recompute; the host float64 values are bitwise the
    legacy scalar model per expander; and with identical traffic the gen4
    expander is strictly slower than the gen5 one."""
    from repro.simx import time as TM
    from repro.simx.engine import TRAFFIC_KEYS

    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=96, seed=4)
    devices = [TM.DeviceConfig(), TM.DEVICE_PROFILES["gen4"]]
    fab = Fabric(cfg, POLICY, StaticInterleave(2, cfg.n_pages), seed=0,
                 rates_table=jnp.asarray(rates), window=WINDOW, spill=False,
                 devices=devices)
    fab.replay(ospn, wr, blk)
    per = fab.delivered_time()                       # float64 host path
    in_jit = fab.delivered_time(exact=False)         # computed in the vmap
    assert per.shape == (2,) and (per > 0).all()
    assert np.allclose(per, in_jit, rtol=1e-4), (per, in_jit)
    for e, c in enumerate(fab.counters_by_expander()):
        internal = sum(c[k] for k in TRAFFIC_KEYS)
        legacy = TM.exec_time_dict(dict(c, internal_accesses=internal),
                                   devices[e])
        assert per[e] == legacy, f"expander {e} drifted from scalar model"
    assert fab.bottleneck_time() == per.max()
    # same counters on the slower generation must cost at least as much
    t_gen4 = TM.exec_time_vec(
        np.asarray(jax.device_get(fab.pools.counters), np.float64),
        TM.DEVICE_PROFILES["gen4"])
    t_gen5 = TM.exec_time_vec(
        np.asarray(jax.device_get(fab.pools.counters), np.float64),
        TM.DeviceConfig())
    assert (t_gen4 > t_gen5).all()


def test_delivered_time_charges_spill_on_the_expander_where_it_occurs():
    """Spill migration traffic lands in the source/donor counters, so the
    donor's delivered time rises above an idle expander's even though it
    serves ZERO host accesses — the per-expander time model sees the
    migration where it physically happened."""
    cfg, placement, fab, (ospn, wr, blk) = _saturating_fabric()
    fab.replay(ospn, wr, blk)
    assert fab.spill_stats()["events"] > 0
    c0, c1 = fab.counters_by_expander()
    assert c1["host_reads"] + c1["host_writes"] == 0
    per = fab.delivered_time()
    assert per[1] > 0, "donor's spill traffic not priced"
    # and the donor's time is exactly its own demo-write/store traffic
    # priced by its own device (internal-bandwidth term; no host terms)
    dev = fab.devices[1]
    internal1 = sum(c1[k] for k in S.TRAFFIC_NAMES)
    assert per[1] == internal1 * 64 / (dev.channels * dev.ch_bw)


def test_mixed_fleet_spill_priced_by_each_expanders_device():
    """A skew-saturated mixed-generation fleet spills, and the migration
    traffic is priced where it happens by that expander's own generation:
    demotion-reads on the starved gen5 source, demotion-writes on the gen4
    donor, each expander's delivered time bitwise the scalar model under
    its own DeviceConfig."""
    from repro.simx import time as TM
    devices = [TM.DeviceConfig(), TM.DEVICE_PROFILES["gen4"]]
    cfg, placement, fab, (ospn, wr, blk) = _saturating_fabric(
        devices=devices)
    fab.replay(ospn, wr, blk)
    assert fab.spill_stats()["events"] > 0, "spill never fired"
    c0, c1 = fab.counters_by_expander()
    assert c0["demo_rd"] > 0 and c1["demo_wr"] > 0
    per = fab.delivered_time()
    for e, c in enumerate((c0, c1)):
        internal = sum(c[k] for k in S.TRAFFIC_NAMES)
        assert per[e] == TM.exec_time_dict(
            dict(c, internal_accesses=internal), devices[e])
    # the donor's spill traffic costs more on gen4 than it would on gen5
    assert per[1] > TM.exec_time_dict(c1, TM.DeviceConfig())


def test_fabric_segment_delta_tracking():
    """track_segments records one per-expander counter delta per replayed
    segment (the async-migration / rebalancing hook): deltas are
    non-negative and sum to the final counters."""
    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=128, seed=5)
    fab = Fabric(cfg, POLICY, StaticInterleave(2, cfg.n_pages), seed=0,
                 rates_table=jnp.asarray(rates), window=WINDOW,
                 spill=True, spill_interval=2 * WINDOW,
                 track_segments=True)
    fab.replay(ospn, wr, blk)
    assert fab.segment_deltas, "no segments recorded"
    assert fab.segment_syncs == len(fab.segment_deltas)
    # no spill fired (plenty of chunk headroom at this scale), so the
    # replay deltas alone must reconstruct the final counters; spill
    # migration charges land between segments and are tracked separately
    # (spill_stats), not inside the per-segment replay deltas
    assert fab.spill_stats()["events"] == 0
    total = np.zeros((2, S.NUM_COUNTERS), np.int64)
    for d in fab.segment_deltas:
        assert d.shape == (2, S.NUM_COUNTERS)
        assert (d >= 0).all()
        total += d
    final = np.asarray(jax.device_get(fab.pools.counters), np.int64)
    assert (total == final).all()


def test_depth1_pipeline_bit_identical_to_sync():
    """The degenerate (depth-1) pipeline — plan and apply at the same
    boundary — must produce bit-identical final pool state, counters, and
    override tables to the synchronous reference driver, on a config
    where migration actually fires. This pins the overlap machinery
    (pending masks, deferral, delayed apply) against the PR 3 parity
    suite: at depth 1 it must all be invisible."""
    cfg, pl_d1, f_d1, tr = _saturating_fabric()
    f_d1.pipeline_depth = 1
    _, pl_sync, f_sync, tr2 = _saturating_fabric()
    f_sync.sync_migration = True
    f_d1.replay(*tr)
    f_sync.replay(*tr2)
    assert f_sync.spill_stats()["events"] > 0, "reference never migrated"
    assert f_d1.state_identical(f_sync), \
        "depth-1 pipeline drifted from the synchronous driver"
    assert f_d1.counters() == f_sync.counters()
    assert f_d1.spill_stats() == f_sync.spill_stats()


def test_overlapped_urgent_spill_keeps_invariants():
    """The default (depth-2) scheduler on the saturating config: pressure
    plans from a starved source are URGENT and apply at the same boundary
    (relief one segment late is relief after the freelists ran dry), so
    invariants hold on both expanders and migration traffic lands on the
    right sides even under overlap."""
    cfg, placement, fab, (ospn, wr, blk) = _saturating_fabric()
    assert fab.pipeline_depth == 2 and not fab.sync_migration
    fab.replay(ospn, wr, blk)
    assert fab.spill_stats()["events"] > 0
    for e in range(2):
        check_pool_invariants(S.pool_slice(fab.pools, e), cfg)
    ss = fab.sync_stats()
    assert ss["segment_syncs"] == ss["segments"]
    assert ss["epoch_syncs"] == ss["epochs"] == fab.placement.epoch
    c0, c1 = fab.counters_by_expander()
    assert c0["demo_rd"] > 0 and c1["demo_wr"] > 0


class _ScriptedOnce(MG.MigrationPolicy):
    """Plans a fixed page set exactly once, when armed (test harness for
    the in-flight deferral path)."""
    name = "scripted"

    def __init__(self):
        self.pages = None
        self.armed = False

    def plan(self, view):
        if not self.armed or self.pages is None:
            return None
        self.armed = False
        k = len(self.pages)
        return MG.MigrationPlan(np.asarray(self.pages, np.int32),
                                np.zeros((k,), np.int32),
                                np.ones((k,), np.int32))


def test_overlapped_migration_defers_inflight_accesses():
    """Depth-2 overlap: accesses to a page whose migration plan is in
    flight are deferred by the carried pending mask and replayed after
    the epoch commits — served (and charged) on the page's FINAL home,
    never on the source mid-migration."""
    cfg = _small_cfg()
    scripted = _ScriptedOnce()
    placement = WeightedInterleave(2, cfg.n_pages, [1.0, 0.0])
    fab = Fabric(cfg, POLICY, placement, seed=0,
                 rates_table=jnp.asarray(
                     np.full((cfg.n_pages, cfg.blocks_per_page), 2,
                             np.int32)),
                 window=WINDOW, migration=scripted,
                 spill_interval=WINDOW)
    # warm: 32 first-touch writes overflow the 16-P-chunk promoted region,
    # demoting early pages into the compressed region (migration-eligible)
    warm = np.arange(32, dtype=np.int32)
    fab.replay(warm, np.ones((32,), bool), np.zeros((32,), np.int32))
    stats = fops.segment_stats(S.pool_slice(fab.pools, 0), cfg)
    eligible = np.nonzero(np.asarray(jax.device_get(stats.eligible)))[0]
    assert len(eligible) >= 4, "warm phase left no eligible pages"
    pages = eligible[:4]
    scripted.pages = pages
    scripted.armed = True
    # segment 1: filler writes (plan fires at its boundary); segment 2:
    # reads of the planned pages — IN FLIGHT, so all deferred; segment 3:
    # more filler. The deferred reads replay after the commit, on e1.
    filler1 = np.arange(32, 40, dtype=np.int32)
    reads = np.concatenate([pages, pages]).astype(np.int32)
    filler2 = np.arange(40, 48, dtype=np.int32)
    ospn = np.concatenate([filler1, reads, filler2])
    wr = np.concatenate([np.ones(8, bool), np.zeros(8, bool),
                         np.ones(8, bool)])
    blk = np.zeros((24,), np.int32)
    before = fab.counters_by_expander()
    assert before[1]["host_reads"] + before[1]["host_writes"] == 0
    fab.replay(ospn, wr, blk)
    assert (placement.route(pages) == 1).all(), "pages did not migrate"
    c0, c1 = fab.counters_by_expander()
    # every deferred read was served by the donor, none leaked to the
    # source mid-migration; writes stayed on e0
    assert c1["host_reads"] == len(reads), (c0["host_reads"],
                                            c1["host_reads"])
    assert c0["host_reads"] == 0
    assert c0["host_writes"] == 48 and c1["host_writes"] == 0
    for e in range(2):
        check_pool_invariants(S.pool_slice(fab.pools, e), cfg)
    ss = fab.sync_stats()
    assert ss["segment_syncs"] == ss["segments"]
    assert ss["epoch_syncs"] == ss["epochs"] == 1


class _ScriptedAlways(MG.MigrationPolicy):
    """Re-plans the same pages at every boundary (livelock-guard probe)."""
    name = "scripted-always"

    def __init__(self, pages):
        self.pages = np.asarray(pages, np.int32)
        self.armed = False

    def plan(self, view):
        if not self.armed:
            return None
        k = len(self.pages)
        return MG.MigrationPlan(self.pages, np.zeros((k,), np.int32),
                                np.ones((k,), np.int32))


def test_unappliable_plan_does_not_livelock():
    """A plan whose every move the apply refuses (here: the page is
    promoted, so ineligible) while the remaining trace keeps accessing
    the planned page would recur forever — deferred accesses rebuild the
    same remainder and the policy re-plans the same page. The livelock
    guard bars zero-progress pages from re-planning, so the replay
    terminates and the deferred accesses are served on the source."""
    cfg = _small_cfg()
    scripted = _ScriptedAlways([0])
    placement = WeightedInterleave(2, cfg.n_pages, [1.0, 0.0])
    fab = Fabric(cfg, POLICY, placement, seed=0,
                 rates_table=jnp.asarray(
                     np.full((cfg.n_pages, cfg.blocks_per_page), 2,
                             np.int32)),
                 window=WINDOW, migration=scripted,
                 spill_interval=WINDOW)
    # page 0 is written once -> promoted (first-touch lands hot; only 4
    # writes, so the demotion watermark never fires) -> never
    # migration-eligible
    warm = np.arange(4, dtype=np.int32)
    fab.replay(warm, np.ones((4,), bool), np.zeros((4,), np.int32))
    scripted.armed = True
    reads = np.concatenate([np.arange(8, 16, dtype=np.int32),
                            np.zeros((16,), np.int32)])
    fab.replay(reads, np.zeros((24,), bool), np.zeros((24,), np.int32))
    c0, c1 = fab.counters_by_expander()
    assert c0["host_reads"] == 24 and c1["host_reads"] == 0
    assert fab.spill_stats()["pages_out"] == [0, 0]
    assert fab._blocked[0], "zero-progress page was not barred"
    assert (placement.overrides == -1).all()


def test_rebalance_reduces_delivered_time_spread():
    """The traffic-imbalance trigger on a 0.8-skewed trace: referenced
    compressed pages migrate hot -> cold, so the per-expander
    delivered-time spread shrinks vs the pressure-only spill policy
    (which never fires here — chunk headroom is ample), and I1–I5 hold
    on source and destination after EVERY migration epoch."""
    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=512, seed=7)

    epochs_checked = []

    def check_epoch(fab, plan, moved):
        for e in range(2):
            check_pool_invariants(S.pool_slice(fab.pools, e), fab.cfg)
        epochs_checked.append(len(moved))

    def run(mode, cb=None):
        fab = Fabric(cfg, POLICY,
                     WeightedInterleave(2, cfg.n_pages, [0.8, 0.2]),
                     seed=0, rates_table=jnp.asarray(rates), window=WINDOW,
                     migration=mode, spill_interval=8 * WINDOW,
                     on_epoch=cb)
        fab.replay(ospn, wr, blk)
        return fab

    fab_spill = run("spill")
    fab_reb = run("rebalance", check_epoch)
    assert fab_spill.spill_stats()["events"] == 0, \
        "pressure spill fired; the comparison is no longer rebalance-only"
    assert fab_reb.epochs_applied > 0 and sum(epochs_checked) > 0, \
        "rebalance trigger never fired"
    t_spill = fab_spill.delivered_time()
    t_reb = fab_reb.delivered_time()
    spread = lambda t: float(t.max() / max(t.min(), 1e-18))  # noqa: E731
    assert spread(t_reb) < spread(t_spill), (t_reb, t_spill)
    ss = fab_reb.sync_stats()
    assert ss["segment_syncs"] == ss["segments"]
    assert ss["epoch_syncs"] == ss["epochs"]
    # rebalance epochs are proactive (never urgent here: headroom is
    # ample), so they genuinely overlapped foreground replay — the
    # pipeline pricing must show a strict win somewhere
    pt = fab_reb.pipeline_times()
    assert pt["mode"] == "overlapped"
    assert (pt["overlapped_s"] <= pt["sync_s"] + 1e-15).all()
    assert (pt["overlapped_s"] < pt["sync_s"]).any(), \
        "no migration epoch was hidden behind replay"


def test_pipeline_pricing_urgent_epochs_stay_on_critical_path():
    """With ``proactive=1.0`` the spill trigger IS the hard watermark, so
    every plan is URGENT and applies synchronously — the pipeline pricing
    must NOT grant those epochs the overlap discount: overlapped and sync
    pricing coincide exactly, even on an overlapped-mode run."""
    cfg, placement, fab, (ospn, wr, blk) = _saturating_fabric()
    fab.migration_policy = MG.SpillPressure(k=8, low=40, proactive=1.0)
    fab.replay(ospn, wr, blk)
    assert fab.epochs_applied > 0
    assert all(not over for _, _, over in fab.migration_deltas), \
        "saturation epochs should all be urgent/synchronous"
    pt = fab.pipeline_times()
    assert pt is not None and pt["mode"] == "overlapped"
    assert (pt["overlapped_s"] == pt["sync_s"]).all(), \
        "urgent epochs were granted the overlap discount"
    assert (pt["delivered_s"] == pt["overlapped_s"]).all()


def test_second_chance_lanes_group_balancing():
    """With groups, the sweep picks the candidate on the least-loaded
    expander (clearing swept ref bits as usual); without, behavior is the
    unchanged clock."""
    sel = SecondChanceLanes(4)
    occupied = np.array([True, True, True, True])
    ref = np.array([False, False, False, False])
    groups = np.array([0, 1, 0, 1])
    load = np.array([5, 0])
    victim, _ = sel.select_mask(occupied, ref, groups=groups,
                                group_load=load)
    assert victim == 1           # first candidate on expander 1 (load 0)
    sel2 = SecondChanceLanes(4)
    victim2, _ = sel2.select_mask(occupied, ref)
    assert victim2 == 0          # plain clock unchanged


def test_serve_engine_parks_per_expander():
    """Fabric-aware serving: lanes stripe across expanders, preempted
    payloads are charged to their lane's expander, and totals reconcile."""
    jax_decode = pytest.importorskip("repro.models.decode")  # noqa: F841
    import jax
    from repro.common.types import ServeConfig
    from repro.configs import get_reduced
    from repro.models import transformer as T
    from repro.serve import Engine

    cfg = get_reduced("llama3_8b")
    scfg = ServeConfig(max_running=2, hot_window=16, attn_chunk=32,
                       kv_rate_bits=8, n_expanders=2)
    params, _ = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, scfg, params, max_len=128)
    assert list(eng.lane_expander) == [0, 1]
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(list(rng.integers(1, cfg.vocab_size, 12 + 2 * i)), 6)
    eng.run_until_done(max_steps=500)
    st = eng.expander_stats
    assert int(st["preempt_bytes"].sum()) == eng.counters["preempt_bytes"]
    assert int(st["resume_bytes"].sum()) == eng.counters["resume_bytes"]
    if eng.counters["demotions"] >= 2:
        # victim balancing spread parks across both expanders
        assert (st["preempt_bytes"] > 0).all()
    assert (st["parked"] >= 0).all()
    assert int(st["parked"].sum()) == sum(
        1 for r in eng.requests.values() if r.parked is not None)
