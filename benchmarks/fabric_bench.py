"""Multi-expander fabric benchmark: delivered-time scaling curves + skew
sensitivity + counter-sum and time-model parity (DESIGN.md §11/§12).

  * **scaling** — the same merged trace replayed through fabrics of
    1/2/4/8 expanders (per-expander pool dimensions fixed, so capacity
    scales with N). Two rates per point: simulator wall-clock accesses/sec
    (steady state, compile excluded — NOTE: under vmap both sides of every
    masked-window branch execute for all expanders, so wall-clock carries
    a documented constant and is NOT the delivered-bandwidth story) and
    **delivered** accesses/sec: expanders serve in parallel, so delivered
    time is the *bottleneck* expander's vectorized device-model time
    (`Fabric.delivered_time`, computed inside the vmapped replay) over its
    own traffic — that is the curve that scales with capacity and
    collapses under skew.
  * **mixed fleets** — heterogeneous generations (`simx.time
    DEVICE_PROFILES`: gen5 default + gen4) under skewed placement with
    spill LIVE: per-expander delivered seconds price each expander's own
    traffic — including migration traffic, charged on the expander where
    it physically occurred — through that expander's own DeviceConfig.
  * **skew** — a 4-expander fabric under WeightedInterleave placement with
    a growing expander-0 page share: delivered rate + per-expander host
    traffic share + spill activity (placement skew, not workload locality,
    is the lever that kills delivered bandwidth on real multi-device CXL).
  * **migration pipeline** — the skew-0.8 4-expander point under the
    ``rebalance`` MigrationPolicy, replayed through the overlapped
    segment scheduler (pipeline depth 2) AND the synchronous reference
    driver: per-segment pipeline pricing (``simx.time
    pipeline_delivered_time``) records sync-vs-overlapped delivered time,
    with overlapped <= sync ASSERTED on the overlapped run's own deltas
    (max <= sum per segment) — and the depth-1 degenerate pipeline is
    asserted BIT-IDENTICAL (pools + counters + overrides) to the
    synchronous driver.
  * **host-sync contract (asserted on every fabric run)** — mirroring
    serve's ``step_syncs == steps``: exactly one host sync per replayed
    segment (the fused stats fetch) and one per committed migration epoch
    (the moved-pages fetch); ``segment_syncs == segments`` and
    ``epoch_syncs == epochs`` are checked machine-side on every
    scaling/fleet/skew/migration point, so the "one sync per pipeline
    stage" claim is enforced, not narrated.
  * **parity (asserted)** — an N=1 fabric is counter-for-counter identical
    to ``batch.replay_trace`` on one pool, and an N=2 fabric's summed
    counters equal the sum of single-pool replays of the merged trace's
    per-expander partitions EXACTLY (static interleave, no spill). Against
    ONE merged pool with N× regions + N× metadata cache, total internal
    traffic agrees within the documented tolerance (shared-vs-sharded
    cache and demotion cadence shift counters; see DESIGN.md §11). The
    vectorized time model is additionally asserted against the legacy
    scalar dict path (bitwise, float64) on every expander of every scaling
    point, and against the in-jit float32 value within 1e-4.

Writes ``BENCH_fabric.json`` at the repo root.
"""
from __future__ import annotations

import json
import pathlib
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import replace
from repro.core.engine import batch as B
from repro.core.engine import state as S
from repro.core.engine.policy import POLICIES
from repro.fabric import Fabric, StaticInterleave, WeightedInterleave
from repro.obs import manifest as run_manifest
from repro.simx import device as DEV
from repro.simx import time as TM
from repro.simx.engine import TRAFFIC_KEYS, pool_cfg_for
from repro.simx.trace import WORKLOADS, make_rates_table, make_trace

JSON_PATH = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_fabric.json"

SCALES = (1, 2, 4, 8)
SKEWS_Q = (0.5, 0.8)           # expander-0 page share at N=4
SKEWS_F = (0.25, 0.5, 0.8)
MERGED_POOL_TOL = 0.35         # documented tolerance vs ONE merged pool
WL = "mcf"

# mixed-generation fleets (profiles cycle across expanders): the gen4
# expanders' slower link/channels/engine make them the delivered-time
# bottleneck even under even placement
FLEETS_Q = {"mixed2": ("default", "gen4")}
FLEETS_F = {"mixed2": ("default", "gen4"),
            "mixed4": ("default", "default", "gen4", "gen4")}


def _fabric(cfg, n, rates, seed, window, placement=None, **kw):
    placement = placement or StaticInterleave(n, cfg.n_pages)
    return Fabric(cfg, POLICIES["ibex"], placement, seed=seed,
                  rates_table=jnp.asarray(rates), window=window, **kw)


def _rate(make, ospn, wr, blk, reps: int):
    """Steady-state accesses/sec: compile+warm once, then min-of-reps on
    fresh fabrics (state shapes identical → jit cache hits). Returns
    (rate, last fabric) so callers read counters without another replay."""
    make().replay(ospn, wr, blk)                  # compile + warm
    best = np.inf
    for _ in range(reps):
        fab = make()
        t0 = time.perf_counter()
        jax.block_until_ready(fab.replay(ospn, wr, blk).pools.counters)
        best = min(best, time.perf_counter() - t0)
    return len(ospn) / best, fab


def _internal(c: Dict[str, int]) -> int:
    return sum(c[k] for k in TRAFFIC_KEYS)


def _sync_contract(fab: Fabric) -> Dict[str, int]:
    """Assert (and record) the segment scheduler's host-sync contract:
    measured syncs must match the budgets `_fetch_view` / `_commit_epoch`
    DECLARE via @sync_contract (one per segment, one per epoch) — the
    bench cross-checks the declaration instead of restating it."""
    from repro.common.contracts import verify_sync_counters
    ss = fab.sync_stats()
    verify_sync_counters(Fabric._fetch_view, ss["segments"],
                         ss["segment_syncs"], what=str(ss))
    verify_sync_counters(Fabric._commit_epoch, ss["epochs"],
                         ss["epoch_syncs"], what=str(ss))
    return ss


def _delivered(fab: Fabric) -> Dict[str, object]:
    """Per-expander + bottleneck delivered seconds, with the time-model
    parity contract asserted: the vectorized float64 path is bitwise what
    the legacy scalar dict model computes per expander, and the in-jit
    float32 values (computed inside the vmapped replay) agree to 1e-4."""
    per = fab.delivered_time()                       # float64, host
    for e, c in enumerate(fab.counters_by_expander()):
        legacy = DEV.exec_time(dict(c, internal_accesses=_internal(c)),
                               fab.devices[e])
        assert per[e] == legacy, \
            f"vectorized time drifted from scalar model on expander {e}"
    in_jit = fab.delivered_time(exact=False)
    assert np.allclose(per, in_jit, rtol=1e-4), (per, in_jit)
    return {"per_expander_s": [float(t) for t in per],
            "bottleneck_s": float(per.max()),
            "bottleneck_expander": int(per.argmax())}


def run(quick: bool, seed: int = 0) -> List[Dict]:
    prom = 32                      # per-expander promoted region
    n_pages = 256                  # shared OSPA page space: N=1 is 8x
    #                                oversubscribed, N=8 fully promotes —
    #                                the capacity side of the scaling story
    n_accesses = 2048 if quick else 8192
    window = 16
    reps = 2 if quick else 4
    cfg = pool_cfg_for(  # per-expander pool dimensions (fixed across N)
        POLICIES["ibex"], n_pages=n_pages, n_pchunks=prom,
        n_cchunks=2 * n_pages * 4)
    spec = WORKLOADS[WL]
    rates = make_rates_table(spec, n_pages, seed=seed)
    ospn, wr, blk = make_trace(spec, n_accesses=n_accesses, n_pages=n_pages,
                               seed=seed)
    rows = []

    # -- delivered-time scaling curve (homogeneous fleets) -------------------
    scaling: Dict[str, Dict[str, float]] = {}
    for n in SCALES:
        t0 = time.perf_counter()
        acc, fab = _rate(lambda n=n: _fabric(cfg, n, rates, seed, window,
                                             spill=False), ospn, wr, blk,
                         reps)
        d = _delivered(fab)
        modeled = n_accesses / d["bottleneck_s"]
        scaling[str(n)] = {
            "wallclock_acc_per_sec": acc,
            "modeled_acc_per_sec": modeled,
            "delivered_time_s": d["bottleneck_s"],
            "delivered_per_expander_s": d["per_expander_s"],
            "internal_accesses": _internal(fab.counters()),
            "sync": _sync_contract(fab),
        }
        rows.append({"name": f"fabric.scale.{n}x",
                     "us": (time.perf_counter() - t0) * 1e6,
                     "derived": f"wall={acc:,.0f}acc/s;"
                                f"delivered={d['bottleneck_s'] * 1e6:.1f}us;"
                                f"modeled={modeled:,.0f}acc/s;"
                                f"internal={_internal(fab.counters())}"})
        if n == 1:
            c_1x = dict(fab.counters(),
                        internal_accesses=_internal(fab.counters()))

    # -- measurement-calibrated engine pricing (tentpole (b)): the N=1
    # delivered-time point re-priced with engine constants derived from the
    # measured kernel throughput in BENCH_kernels.json (paper constants when
    # the bench artifact is absent)
    cal_dev = TM.calibrated_device()
    paper_dev = TM.DeviceConfig()
    calibration = {
        "source": "BENCH_kernels.json" if cal_dev != paper_dev
                  else "paper-fallback",
        "comp_cycles": cal_dev.comp_cycles,
        "decomp_cycles": cal_dev.decomp_cycles,
        "paper_comp_cycles": paper_dev.comp_cycles,
        "paper_decomp_cycles": paper_dev.decomp_cycles,
        "delivered_time_s_1x_paper": float(DEV.exec_time(c_1x, paper_dev)),
        "delivered_time_s_1x_calibrated":
            float(DEV.exec_time(c_1x, cal_dev)),
    }
    rows.append({"name": "fabric.calibrated_1x", "us": 0.0,
                 "derived": f"paper={calibration['delivered_time_s_1x_paper'] * 1e6:.1f}us;"
                            f"calibrated={calibration['delivered_time_s_1x_calibrated'] * 1e6:.1f}us;"
                            f"src={calibration['source']}"})

    # -- mixed-generation fleets (spill live, skewed placement) --------------
    # the fleet rows shrink the per-expander compressed region so the 0.8
    # page skew genuinely starves expander 0's freelists and the spill path
    # fires — the JSON then shows migration traffic charged per expander
    # (source demo_rd, donor demo_wr) and priced by each expander's own
    # device generation
    fleet_cfg = replace(cfg, n_cchunks=256)
    mixed: Dict[str, Dict[str, object]] = {}
    for name, profiles in (FLEETS_Q if quick else FLEETS_F).items():
        n = len(profiles)
        devices = [TM.DEVICE_PROFILES[p] for p in profiles]
        share = 0.8
        restw = (1.0 - share) / max(n - 1, 1)
        mk = lambda n=n, devices=devices, restw=restw: _fabric(
            fleet_cfg, n, rates, seed, window,
            placement=WeightedInterleave(n, n_pages,
                                         [share] + [restw] * (n - 1)),
            spill=True, spill_interval=512, spill_k=16, spill_low=112,
            devices=devices)
        t0 = time.perf_counter()
        acc, fab = _rate(mk, ospn, wr, blk, reps)
        d = _delivered(fab)
        per = fab.counters_by_expander()
        assert fab.spill_stats()["events"] > 0, \
            f"fleet {name}: spill never fired (deterministic config)"
        mixed[name] = {
            "profiles": list(profiles),
            "wallclock_acc_per_sec": acc,
            "modeled_acc_per_sec": n_accesses / d["bottleneck_s"],
            "delivered_time_s": d["bottleneck_s"],
            "delivered_per_expander_s": d["per_expander_s"],
            "bottleneck_expander": d["bottleneck_expander"],
            "internal_per_expander": [_internal(c) for c in per],
            "host_per_expander": [c["host_reads"] + c["host_writes"]
                                  for c in per],
            # spill traffic is charged on the expander where it occurs:
            # demo_rd on the starved source, demo_wr on the donor
            "spill": fab.spill_stats(),
            "spill_demo_rd_per_expander": [c["demo_rd"] for c in per],
            "spill_demo_wr_per_expander": [c["demo_wr"] for c in per],
            "sync": _sync_contract(fab),
        }
        rows.append({"name": f"fabric.fleet.{name}",
                     "us": (time.perf_counter() - t0) * 1e6,
                     "derived": f"delivered={d['bottleneck_s'] * 1e6:.1f}us;"
                                f"bottleneck=e{d['bottleneck_expander']};"
                                f"spills={fab.spill_stats()['events']}"})

    # -- skew sweep (N=4, spill live) ---------------------------------------
    skew_rows = {}
    for share in (SKEWS_Q if quick else SKEWS_F):
        rest = (1.0 - share) / 3.0
        mk = lambda share=share, rest=rest: _fabric(
            cfg, 4, rates, seed, window,
            placement=WeightedInterleave(4, n_pages,
                                         [share, rest, rest, rest]),
            spill=True, spill_interval=1024)
        t0 = time.perf_counter()
        acc, fab = _rate(mk, ospn, wr, blk, reps)
        per = fab.counters_by_expander()
        host = [c["host_reads"] + c["host_writes"] for c in per]
        d = _delivered(fab)
        modeled = n_accesses / d["bottleneck_s"]
        pages = np.bincount(fab.placement.assign(np.arange(n_pages)),
                            minlength=4) / n_pages
        # page share is what the placement controls; access share also
        # depends on which zipf-head pages the hash lands on each expander
        skew_rows[f"{share:.2f}"] = {
            "wallclock_acc_per_sec": acc,
            "modeled_acc_per_sec": modeled,
            "delivered_time_s": d["bottleneck_s"],
            "page_share": pages.tolist(),
            "host_share": [h / max(sum(host), 1) for h in host],
            "spill": fab.spill_stats(),
            "sync": _sync_contract(fab),
        }
        rows.append({"name": f"fabric.skew.{share:.2f}",
                     "us": (time.perf_counter() - t0) * 1e6,
                     "derived": f"modeled={modeled:,.0f}acc/s;"
                                f"e0_pages={pages[0]:.2f};"
                                f"e0_host={host[0] / max(sum(host), 1):.2f};"
                                f"spills={fab.spill_stats()['events']}"})

    # -- sync-vs-overlapped migration pipeline (skew 0.8, N=4, rebalance) ----
    # the acceptance point: the overlapped segment scheduler's pipeline
    # pricing (max(replay, migration) per segment) against the synchronous
    # reference (replay + migration). overlapped <= sync is asserted on the
    # overlapped run's OWN deltas (mathematically max <= sum, so a violation
    # means the accounting broke); the depth-1 degenerate pipeline must be
    # bit-identical to the synchronous driver (pools + counters + overrides)
    mig_share = 0.8
    mig_rest = (1.0 - mig_share) / 3.0

    def mk_mig(**kw):
        return _fabric(cfg, 4, rates, seed, window,
                       placement=WeightedInterleave(
                           4, n_pages, [mig_share] + [mig_rest] * 3),
                       migration="rebalance", spill_interval=1024, **kw)

    t0 = time.perf_counter()
    fab_over = mk_mig(pipeline_depth=2)
    fab_over.replay(ospn, wr, blk)
    pt_over = fab_over.pipeline_times()
    _sync_contract(fab_over)
    fab_sync = mk_mig(sync_migration=True)
    fab_sync.replay(ospn, wr, blk)
    pt_sync = fab_sync.pipeline_times()
    _sync_contract(fab_sync)
    over_s = float(np.max(pt_over["overlapped_s"]))
    over_sync_s = float(np.max(pt_over["sync_s"]))
    sync_s = float(np.max(pt_sync["sync_s"]))
    assert (pt_over["overlapped_s"] <= pt_over["sync_s"] + 1e-15).all(), \
        "overlapped pricing exceeded sync pricing on the same deltas"
    _delivered(fab_over)     # per-expander counter/time parity, asserted

    fab_d1 = mk_mig(pipeline_depth=1)
    fab_d1.replay(ospn, wr, blk)
    fab_ref = mk_mig(sync_migration=True)
    fab_ref.replay(ospn, wr, blk)
    identical = fab_d1.state_identical(fab_ref)
    assert identical, "depth-1 pipeline drifted from the synchronous driver"

    migration = {
        "placement": f"weighted {mig_share:.2f} skew, 4 expanders",
        "policy": "rebalance",
        # the apples-to-apples pair (same run, same deltas, two pricings;
        # overlapped <= sync asserted): what the pipeline hides
        "overlapped_s": over_s,
        "sync_s": over_sync_s,
        "overlap_hidden_s": over_sync_s - over_s,
        # a separate run through the synchronous driver (its own migration
        # timing, so its counters differ slightly — informational)
        "sync_reference_run_s": sync_s,
        "overlapped_per_expander_s": [float(t)
                                      for t in pt_over["overlapped_s"]],
        "sync_per_expander_s": [float(t) for t in pt_over["sync_s"]],
        "epochs_overlapped": fab_over.epochs_applied,
        "epochs_sync": fab_sync.epochs_applied,
        "pages_moved_overlapped": int(fab_over.spill_pages_out.sum()),
        "sync_contract": _sync_contract(fab_over),
        "depth1_bit_identical_to_sync": bool(identical),
    }
    rows.append({"name": "fabric.migration.overlap",
                 "us": (time.perf_counter() - t0) * 1e6,
                 "derived": f"overlapped={over_s * 1e6:.1f}us;"
                            f"sync={over_sync_s * 1e6:.1f}us;"
                            f"hidden={(over_sync_s - over_s) * 1e6:.2f}us;"
                            f"epochs={fab_over.epochs_applied};"
                            f"depth1=bit-identical"})

    # -- telemetry piggyback A/B (DESIGN.md §16) ------------------------------
    # the SAME rebalance point replayed with an obs.Recorder attached.
    # Asserted: pool/counter state is bit-identical to the recording-off
    # run, the declared sync budgets still hold with the recorder draining
    # every fetch, the exported Perfetto per-expander track totals
    # reconcile with pipeline_times (same row matrices, same pricing), and
    # the trace validates (nesting + monotone timestamps). Wall-clock
    # overhead is recorded (warm same-run A/B; the ≤5% acceptance number)
    # rather than hard-asserted — shared-box preemption noise dwarfs it.
    from repro.obs import Recorder
    from repro.obs import export as OBX
    t0 = time.perf_counter()
    rec = Recorder()
    t_on0 = time.perf_counter()
    fab_rec = mk_mig(pipeline_depth=2, obs=rec)
    fab_rec.replay(ospn, wr, blk)
    t_on = time.perf_counter() - t_on0
    t_off0 = time.perf_counter()
    fab_off = mk_mig(pipeline_depth=2)
    fab_off.replay(ospn, wr, blk)
    t_off = time.perf_counter() - t_off0
    assert fab_rec.state_identical(fab_off), \
        "recording changed pool/counter state"
    sync_rec = _sync_contract(fab_rec)     # budgets unchanged, recorder ON
    pt_rec = fab_rec.pipeline_times()
    totals = OBX.fabric_track_totals(rec)
    assert np.allclose(totals["overlapped_s"], pt_rec["overlapped_s"],
                       rtol=1e-9), "trace totals drifted from pipeline_times"
    assert np.allclose(totals["sync_s"], pt_rec["sync_s"], rtol=1e-9), \
        "trace sync totals drifted from pipeline_times"
    trace = OBX.build_trace(rec)
    errors = OBX.validate_trace(trace)
    assert not errors, errors
    overhead = t_on / max(t_off, 1e-12)
    obs_ab = {
        "state_bit_identical": True,
        "sync": sync_rec,
        "segments_recorded": len(rec.segments),
        "epochs_recorded": len(rec.epochs),
        "plans_recorded": len(rec.plans),
        "trace_events": len(trace["traceEvents"]),
        "trace_valid": True,
        "track_totals_reconcile_pipeline_times": True,
        "wallclock_overhead_ratio": overhead,
        "counters": rec.metrics.snapshot()["counters"],
    }
    rows.append({"name": "fabric.obs.ab",
                 "us": (time.perf_counter() - t0) * 1e6,
                 "derived": f"overhead=x{overhead:.3f};bit_identical=True;"
                            f"events={len(trace['traceEvents'])};"
                            f"reconciled=True"})

    # -- parity (asserted) ---------------------------------------------------
    fab1 = _fabric(cfg, 1, rates, seed, window, spill=False)
    fab1.replay(ospn, wr, blk)
    pool1 = S.pool_slice(S.make_pool_stack(cfg, 1, seed=seed,
                                           rates_table=jnp.asarray(rates)), 0)
    pool1 = B.replay_trace(pool1, cfg,
                           fab1.policy, ospn, wr, blk, window=window)
    assert fab1.counters() == S.counters_dict(pool1), \
        "N=1 fabric drifted from single-pool replay"

    placement = StaticInterleave(2, n_pages)
    fab2 = _fabric(cfg, 2, rates, seed, window, placement=placement,
                   spill=False)
    fab2.replay(ospn, wr, blk)
    eids = placement.route(ospn)
    stack0 = S.make_pool_stack(cfg, 2, seed=seed,
                               rates_table=jnp.asarray(rates))
    total = {k: 0 for k in S.COUNTER_NAMES}
    for e in range(2):
        sel = eids == e
        ref = B.replay_trace(S.pool_slice(stack0, e), cfg, fab2.policy,
                             ospn[sel], wr[sel], blk[sel], window=window)
        for k, v in S.counters_dict(ref).items():
            total[k] += v
    assert fab2.counters() == total, \
        "N=2 fabric counter sums drifted from per-shard single-pool replays"

    merged_cfg = replace(cfg, n_pchunks=cfg.n_pchunks * 2,
                         n_cchunks=cfg.n_cchunks * 2,
                         mcache_sets=cfg.mcache_sets * 2)
    poolm = S.make_pool(merged_cfg, seed=seed,
                        rates_table=jnp.asarray(rates))
    poolm = B.replay_trace(poolm, merged_cfg, fab2.policy, ospn, wr, blk,
                           window=window)
    cm = S.counters_dict(poolm)
    rel = abs(_internal(fab2.counters()) - _internal(cm)) / \
        max(_internal(cm), 1)
    assert rel < MERGED_POOL_TOL, (rel, MERGED_POOL_TOL)
    rows.append({"name": "fabric.parity", "us": 0.0,
                 "derived": f"per_shard=exact;merged_pool_rel={rel:.3f}"
                            f"(tol={MERGED_POOL_TOL})"})

    # ---- sharded scaling (DESIGN.md §17), in this process over the
    # devices that exist: asserts bit-identity vs the vmap oracle, the
    # sharded sync budgets, the D-invariant modeled curve, and the
    # device-track reconciliation per point
    from benchmarks import fabric_sharded
    sharded = fabric_sharded.run(quick, seed)
    rows.append({"name": "fabric.sharded",
                 "us": 0.0,
                 "derived": ";".join(
                     f"D{d}={p['wallclock_acc_per_sec']:,.0f}acc/s"
                     for d, p in sorted(sharded["scales"].items(),
                                        key=lambda kv: int(kv[0])))})

    payload = {
        "meta": {**run_manifest(seed=seed),
                 "workload": WL, "n_accesses": n_accesses,
                 "promoted_pages_per_expander": prom, "n_pages": n_pages,
                 "window": window, "reps": reps,
                 "quick": quick,
                 "unit": "accesses/sec; wallclock = simulator steady state "
                         "(compile excluded; vmapped masked branches carry "
                         "a constant), delivered/modeled = bottleneck "
                         "expander's vectorized device-model time computed "
                         "inside the vmapped replay (the delivered-"
                         "bandwidth curve; per-expander DeviceConfig, "
                         "spill traffic charged where it occurs)"},
        "scaling": scaling,
        "calibration": calibration,
        "mixed_fleets": mixed,
        "skew": skew_rows,
        "migration": migration,
        "obs": obs_ab,
        "sharded": sharded,
        "parity": {"per_shard_exact": True,
                   "merged_pool_rel_diff": rel,
                   "merged_pool_tolerance": MERGED_POOL_TOL,
                   "scalar_vs_vectorized_time": "bitwise (asserted per "
                                                "expander on every scaling/"
                                                "fleet/skew point)"},
    }
    JSON_PATH.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    rows.append({"name": "fabric.json", "us": 0.0,
                 "derived": f"json={JSON_PATH.name}"})
    return rows
