"""Telemetry subsystem tests (DESIGN.md §16): the piggyback contract.

  * recording on vs off is BIT-IDENTICAL in pool/counter state — the
    Recorder only consumes host values the contracted fetches already
    produced, so attaching it cannot perturb the run;
  * the declared sync budgets hold with the Recorder attached:
    ``segment_syncs == segments``, ``epoch_syncs == epochs`` — zero extra
    syncs, asserted against the ``@sync_contract`` declarations, not bench
    constants (the serve engine's spans: tests/test_spans.py);
  * the Perfetto export validates (spans nest, timestamps monotone per
    track) and its per-expander track totals reconcile with
    ``Fabric.pipeline_times()`` — the trace is the same accounting, drawn;
  * histogram merge is associative (fixed bounds, bucket-wise add), so
    partial aggregations compose in any order;
  * ``manifest()`` stamps the run facts every BENCH_*.json shares.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.contracts import verify_sync_counters
from repro.core.engine.policy import POLICIES
from repro.fabric import Fabric, WeightedInterleave
from repro.obs import Recorder, manifest
from repro.obs import export as OBX
from repro.obs.registry import Histogram, MetricsRegistry, merge_histograms
from repro.simx.engine import pool_cfg_for
from repro.simx.trace import WORKLOADS, make_rates_table, make_trace

POLICY = POLICIES["ibex"]
WINDOW = 8


# -- shared fixtures ---------------------------------------------------------

def _small_cfg(prom=16, n_pages=64):
    return pool_cfg_for(POLICY, n_pages=n_pages, n_pchunks=prom,
                        n_cchunks=2 * n_pages * 8)


def _trace(cfg, n_accesses, seed=0, wl="mcf"):
    spec = WORKLOADS[wl]
    rates = make_rates_table(spec, cfg.n_pages, seed=seed)
    ospn, wr, blk = make_trace(spec, n_accesses=n_accesses,
                               n_pages=cfg.n_pages, seed=seed)
    return rates, ospn, wr, blk


def _rebalance_fabric(cfg, rates, obs=None):
    """The migration-live operating point (2 expanders, 0.8 skew,
    rebalance policy, overlapped pipeline) — the configuration where the
    Recorder sees segments, plans AND epochs."""
    return Fabric(cfg, POLICY, WeightedInterleave(2, cfg.n_pages, [0.8, 0.2]),
                  seed=0, rates_table=jnp.asarray(rates), window=WINDOW,
                  migration="rebalance", spill_interval=8 * WINDOW, obs=obs)


@pytest.fixture(scope="module")
def recorded_fabric():
    cfg = _small_cfg()
    rates, ospn, wr, blk = _trace(cfg, n_accesses=512, seed=7)
    rec = Recorder()
    fab = _rebalance_fabric(cfg, rates, obs=rec)
    fab.replay(ospn, wr, blk)
    return cfg, rates, (ospn, wr, blk), rec, fab


# -- fabric: bit-identity + sync budgets -------------------------------------

def test_fabric_recording_is_bit_identical(recorded_fabric):
    """Attaching a Recorder changes NOTHING device-side: every pool leaf
    and every counter of the recorded run equals the recording-off run."""
    cfg, rates, (ospn, wr, blk), rec, fab_on = recorded_fabric
    fab_off = _rebalance_fabric(cfg, rates)
    fab_off.replay(ospn, wr, blk)
    assert fab_on.state_identical(fab_off), \
        "recording perturbed pool/counter state"
    assert fab_on.counters() == fab_off.counters()


def test_fabric_sync_budgets_hold_with_recorder(recorded_fabric):
    """Zero extra syncs: the measured per-segment/per-epoch sync counts
    with the Recorder draining every fetch match the @sync_contract
    budgets exactly, and the Recorder saw every one of those events."""
    _, _, _, rec, fab = recorded_fabric
    ss = fab.sync_stats()
    assert ss["segment_syncs"] == ss["segments"]
    assert ss["epoch_syncs"] == ss["epochs"]
    verify_sync_counters(Fabric._fetch_view, ss["segments"],
                         ss["segment_syncs"], what=str(ss))
    verify_sync_counters(Fabric._commit_epoch, ss["epochs"],
                         ss["epoch_syncs"], what=str(ss))
    assert len(rec.segments) == ss["segments"]
    assert len(rec.epochs) == ss["epochs"]
    assert ss["epochs"] > 0, "rebalance point recorded no epochs"
    # the metrics registry aggregated the same deltas the scheduler kept:
    # summed replay deltas == the name-keyed fabric.* counter metrics
    from repro.core.engine import state as S
    snap = rec.metrics.snapshot()["counters"]
    total = int(sum(d["delta"].sum() for d in rec.segments))
    agg = sum(snap.get(f"fabric.{name}", 0) for name in S.COUNTER_NAMES)
    assert total == agg


def test_fabric_trace_validates_and_reconciles(recorded_fabric, tmp_path):
    """The exported Perfetto timeline is well-formed AND is the same
    accounting as ``pipeline_times()``: rebuilding the per-expander track
    totals from the recorded samples reproduces the scheduler's overlapped
    and sync delivered seconds to float64 tolerance."""
    _, _, _, rec, fab = recorded_fabric
    pt = fab.pipeline_times()
    totals = OBX.fabric_track_totals(rec)
    assert np.allclose(totals["overlapped_s"], pt["overlapped_s"],
                       rtol=1e-9), (totals, pt)
    assert np.allclose(totals["sync_s"], pt["sync_s"], rtol=1e-9)
    trace = OBX.build_trace(rec)
    assert OBX.validate_trace(trace) == []
    # a track per expander for replay and one for migration epochs
    tids = {(ev["pid"], ev["tid"]) for ev in trace["traceEvents"]
            if ev["ph"] == "X"}
    assert {(1, 0), (1, 2)} <= tids, tids            # replay tracks e0/e1
    assert any(t in tids for t in [(1, 1), (1, 3)]), \
        "no migration track emitted on a migration-live run"
    path = tmp_path / "fabric.trace.json"
    OBX.write_trace(rec, path)
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"] and on_disk["otherData"]["manifest"]
    mpath = tmp_path / "fabric.metrics.json"
    OBX.write_metrics(rec, mpath, seed=7)
    snap = json.loads(mpath.read_text())
    assert snap["manifest"]["seed"] == 7
    assert snap["fabric"]["epochs"] == len(rec.epochs)
    assert "fabric.pages_moved" in snap["metrics"]["counters"]
    # the human-readable summary covers every pipeline row
    table = OBX.fabric_summary_table(rec)
    assert table.count("\n") >= len(rec.segments)


def test_trace_validator_rejects_malformed():
    """The validator actually checks something: out-of-order timestamps
    on one track and a span overrunning its parent are both findings."""
    base = {"otherData": {}, "displayTimeUnit": "ms"}
    bad_order = dict(base, traceEvents=[
        {"ph": "X", "pid": 1, "tid": 0, "ts": 10.0, "dur": 1.0, "name": "a"},
        {"ph": "X", "pid": 1, "tid": 0, "ts": 5.0, "dur": 1.0, "name": "b"},
    ])
    assert OBX.validate_trace(bad_order)
    bad_nest = dict(base, traceEvents=[
        {"ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 5.0, "name": "p"},
        {"ph": "X", "pid": 1, "tid": 0, "ts": 2.0, "dur": 10.0, "name": "c"},
    ])
    assert OBX.validate_trace(bad_nest)
    bad_phase = dict(base, traceEvents=[
        {"ph": "B", "pid": 1, "tid": 0, "ts": 0.0, "name": "p"},
    ])
    assert OBX.validate_trace(bad_phase)


# -- registry ------------------------------------------------------------------

def test_histogram_merge_is_associative_and_pure():
    bounds = (1.0, 2.0, 5.0, 10.0)
    rng = np.random.default_rng(0)
    hs = []
    for i in range(3):
        h = Histogram(f"h{i}", bounds)
        for v in rng.uniform(0, 15, size=50):
            h.observe(float(v))
        hs.append(h)
    a, b, c = hs
    left = a.merge(b).merge(c)
    right = a.merge(b.merge(c))
    assert left.snapshot() == right.snapshot()
    folded = merge_histograms(hs)
    assert folded.snapshot() == left.snapshot()
    assert left.n == 150 and sum(left.counts) == 150
    # merge returned NEW histograms — inputs untouched
    assert a.n == 50 and b.n == 50 and c.n == 50
    with pytest.raises(ValueError):
        a.merge(Histogram("other", (1.0, 2.0)))


def test_registry_get_or_create_and_counter_monotonicity():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    reg.counter("x").inc(3)
    with pytest.raises(ValueError):
        reg.counter("x").inc(-1)
    reg.gauge("g").set(2.5)
    reg.histogram("h", (1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["counters"] == {"x": 3}
    assert snap["gauges"] == {"g": 2.5}
    assert snap["histograms"]["h"]["count"] == 1


# -- manifest --------------------------------------------------------------------

def test_manifest_stamps_run_facts():
    m = manifest(seed=3, suite="test")
    for key in ("python", "platform", "git_sha", "jax", "jaxlib",
                "backend", "device_count"):
        assert key in m
    assert m["seed"] == 3 and m["suite"] == "test"
    # jax IS importable in this test process, so the stamp must be live
    assert m["jax"] is not None and m["backend"] is not None
    json.dumps(m)   # JSON-serializable by construction
