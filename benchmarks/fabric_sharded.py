"""Sharded fabric scaling bench (DESIGN.md §17) — the ``sharded`` section of
``BENCH_fabric.json``, run in-process by ``fabric_bench.py`` over the
devices that exist (a chip belongs to one process, so nothing here starts
a child).

Standalone, it prints the section as one JSON document. On the CPU,
``--host-devices 8`` forces eight XLA host devices first (jax initializes
its backend lazily, so the flag still takes effect after ``import jax``)
to rehearse the mesh sizes without a chip:

  PYTHONPATH=src JAX_PLATFORMS=cpu python benchmarks/fabric_sharded.py \
      --quick --host-devices 8

What it measures, at a fixed N=8-expander fabric under 0.8 placement
skew with the spill path LIVE, for mesh sizes D in {1, 2, 4, 8}:

  * **wall-clock accesses/sec** — steady state, compile excluded
    (min-of-reps on fresh fabrics; the jit cache is keyed on the Mesh so
    reps hit it). Forced host devices share the box's physical cores, so
    on a small machine the curve shows dispatch overhead, not real
    scaling — the MODELED delivered curve next to it is the bandwidth
    story, exactly as the vmap scaling section documents for its
    wall-clock column.
  * **modeled delivered accesses/sec** — the bottleneck expander's
    float64 device-model time over its own traffic, same pricing as the
    vmap sections (the counters are bit-identical, so the modeled curve
    is D-invariant by construction — asserted).
  * **bit-identity (asserted per point)** — every leaf of the sharded
    end state (counters included) equals the vmap synchronous reference
    via ``state_identical``, and per-expander counter dicts match
    exactly.
  * **host-sync contract (asserted per point)** — measured boundary /
    drain syncs match the budgets ``_commit_boundary`` /
    ``_drain_deferred`` declare via ``@sync_contract``, and the epoch
    host-sync total is STRICTLY below the PR 5 pipelined driver's on the
    same trace (one fused fetch per boundary vs one per segment plus one
    per epoch).
  * **per-device observability** — ``Fabric.device_times()`` reconciled
    against the Recorder-reconstructed per-device Perfetto track totals
    at rtol=1e-9 (the §16 contract extended to device tracks), zero
    extra syncs.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.contracts import verify_sync_counters
from repro.common.sharding import force_host_device_count
from repro.common.types import replace
from repro.core.engine.policy import POLICIES
from repro.fabric import Fabric, WeightedInterleave
from repro.obs import Recorder
from repro.obs import export as OBX
from repro.simx.engine import pool_cfg_for
from repro.simx.trace import WORKLOADS, make_rates_table, make_trace

N_EXP = 8
SCALES = (1, 2, 4, 8)
WL = "mcf"


def _verify_sharded_contract(fab: Fabric) -> Dict[str, int]:
    """Runtime cross-check of the sharded driver's declared budgets: one
    fused fetch per boundary, one deferred drain per replay() call, and
    nothing on the vmap counters."""
    ss = fab.sync_stats()
    verify_sync_counters(Fabric._commit_boundary, ss["boundaries"],
                         ss["boundary_syncs"], what=str(ss))
    assert ss["segment_syncs"] == 0 and ss["epoch_syncs"] == 0, ss
    return ss


def run(quick: bool, seed: int) -> Dict[str, object]:
    n_pages = 256
    n_accesses = 2048 if quick else 8192
    window = 16
    reps = 2 if quick else 4
    cfg = replace(pool_cfg_for(POLICIES["ibex"], n_pages=n_pages,
                               n_pchunks=32, n_cchunks=2 * n_pages * 4),
                  n_cchunks=256)   # shrink so the 0.8 skew starves e0
    spec = WORKLOADS[WL]
    rates = make_rates_table(spec, n_pages, seed=seed)
    ospn, wr, blk = make_trace(spec, n_accesses=n_accesses,
                               n_pages=n_pages, seed=seed)
    share = 0.8
    restw = (1.0 - share) / (N_EXP - 1)

    def mk(**kw):
        return Fabric(cfg, POLICIES["ibex"],
                      WeightedInterleave(N_EXP, n_pages,
                                         [share] + [restw] * (N_EXP - 1)),
                      seed=seed, rates_table=jnp.asarray(rates),
                      window=window, spill=True, spill_interval=512,
                      spill_k=8, spill_low=112, **kw)

    # vmap references on the same trace: the synchronous driver is the
    # bit-identity oracle; the PR 5 pipelined driver sets the host-sync
    # bar the sharded path must beat
    ref = mk(sync_migration=True)
    ref.replay(ospn, wr, blk)
    assert ref.spill_stats()["events"] > 0, \
        "spill never fired (deterministic config) — the bench point is dead"
    ref_counters = ref.counters_by_expander()
    pipe = mk(pipeline_depth=2)
    pipe.replay(ospn, wr, blk)
    pipe_syncs = pipe.sync_stats()["host_syncs"]

    if jax.devices()[0].platform == "cpu" and \
            jax.device_count() < max(SCALES):
        raise RuntimeError(
            f"the sharded sweep needs {max(SCALES)} CPU devices, "
            f"{jax.device_count()} visible: force them before jax "
            f"initializes (--host-devices {max(SCALES)} here, "
            f"benchmarks/run.py does it itself)")
    # a TPU host sweeps the chips it has
    scales = tuple(d for d in SCALES if d <= jax.device_count())
    points: Dict[str, Dict[str, object]] = {}
    for d in scales:
        t0 = time.perf_counter()
        mk(shard_devices=d).replay(ospn, wr, blk)      # compile + warm
        compile_s = time.perf_counter() - t0
        best = np.inf
        for _ in range(reps):
            fab = mk(shard_devices=d)
            t0 = time.perf_counter()
            jax.block_until_ready(
                fab.replay(ospn, wr, blk).pools.counters)
            best = min(best, time.perf_counter() - t0)

        # bit-identity vs the vmap oracle, per expander and per leaf
        assert fab.state_identical(ref), \
            f"D={d}: sharded end state drifted from the vmap reference"
        assert fab.counters_by_expander() == ref_counters, \
            f"D={d}: per-expander counters drifted"

        ss = _verify_sharded_contract(fab)
        assert ss["host_syncs"] < pipe_syncs, \
            (f"D={d}: sharded path used {ss['host_syncs']} host syncs, "
             f"not below the pipelined driver's {pipe_syncs}")

        per = fab.delivered_time()            # float64 exact, one fetch
        bottleneck = float(per.max())
        dt = fab.device_times()
        points[str(d)] = {
            "wallclock_acc_per_sec": n_accesses / best,
            "modeled_acc_per_sec": n_accesses / bottleneck,
            "delivered_time_s": bottleneck,
            "delivered_per_expander_s": [float(t) for t in per],
            "device_s": [float(t) for t in dt["device_s"]],
            "compile_s": compile_s,
            "sync": ss,
            "spill": fab.spill_stats(),
            "bit_identical_to_vmap": True,
        }
        print(f"  D={d}: wall={n_accesses / best:,.0f}acc/s "
              f"modeled={n_accesses / bottleneck:,.0f}acc/s "
              f"syncs={ss['host_syncs']}<{pipe_syncs} identical=True",
              file=sys.stderr)

    # modeled curve is D-invariant (bit-identical counters, same pricing)
    modeled = {k: p["modeled_acc_per_sec"] for k, p in points.items()}
    assert len({round(v, 6) for v in modeled.values()}) == 1, modeled

    # per-device track reconciliation on the largest mesh (obs
    # satellite): Recorder attached, state still bit-identical, device
    # track totals equal Fabric.device_times at rtol=1e-9, trace validates
    rec = Recorder()
    fab_rec = mk(shard_devices=scales[-1], obs=rec)
    fab_rec.replay(ospn, wr, blk)
    assert fab_rec.state_identical(ref), "recording changed sharded state"
    dt = fab_rec.device_times()
    tot = OBX.fabric_device_totals(rec)
    assert np.allclose(tot["device_s"], dt["device_s"], rtol=1e-9), \
        (tot["device_s"], dt["device_s"])
    trace = OBX.build_trace(rec)
    errs = OBX.validate_trace(trace)
    assert not errs, errs[:5]
    n_dev_spans = sum(1 for e in trace["traceEvents"]
                      if e["ph"] == "X" and e.get("tid", 0) >= 1000)
    assert n_dev_spans > 0

    return {
        "meta": {"n_expanders": N_EXP, "n_accesses": n_accesses,
                 "n_pages": n_pages, "window": window, "reps": reps,
                 "workload": WL, "placement_skew": share,
                 "platform": jax.devices()[0].platform,
                 "device_count": jax.device_count(),
                 "obs_mesh_devices": scales[-1],
                 "unit": "accesses/sec; wallclock = host clock (on the "
                         "CPU, forced host devices share the physical "
                         "cores: a dispatch-overhead curve), modeled = "
                         "bottleneck expander's device-model time "
                         "(D-invariant, asserted)"},
        "scales": points,
        "pipelined_reference_host_syncs": pipe_syncs,
        "sync_reference_host_syncs": ref.sync_stats()["host_syncs"],
        "obs": {"device_tracks_reconcile_device_times": True,
                "device_track_spans": n_dev_spans,
                "state_bit_identical_with_recorder": True,
                "device_s": [float(t) for t in dt["device_s"]]},
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--host-devices", type=int, default=0, metavar="N",
                    help="force N XLA host devices (CPU rehearsal of the "
                         "mesh sizes; never needed on a chip)")
    args = ap.parse_args()
    if args.host_devices:
        force_host_device_count(args.host_devices)
    payload = run(args.quick, args.seed)
    json.dump(payload, sys.stdout)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
