"""Multi-expander fabric launcher: replay a paper workload through a fabric
of N simulated expanders with a chosen placement mode (DESIGN.md §11/§13).

  PYTHONPATH=src python -m repro.launch.fabric --workload mcf --expanders 4 \
      --placement interleave --accesses 4096 --seed 0

``--skew`` forces a weighted placement that sends that fraction of pages to
expander 0 (migration stress); ``--migration {spill,rebalance,off}`` picks
the MigrationPolicy (spill = freelist pressure, rebalance = pressure +
traffic-imbalance trigger); ``--sync-migration`` forces the synchronous
reference driver (PR 3 semantics: migration on the critical path);
``--pipeline-depth 1`` runs the pipelined scheduler degenerately (plan and
apply at the same boundary). ``--verify-depth1`` replays the same trace
through BOTH and asserts final pool state + counters are bit-identical
(the refactor's parity pin — the CI smoke). ``--check-parity``
additionally replays every expander's partition through the single-pool
engine and asserts the summed counters match the fabric exactly.

``--devices N`` runs the sharded driver (DESIGN.md §17): the stacked
pool pytree is placed on an N-device ``expander`` mesh and replayed
shard_map-ed, with migration planned and applied inside the jit (one
fused host fetch per boundary). The forced host-device count must reach
XLA before its backend initializes — the repro imports below pull in
jax — so the flag is pre-scanned from argv and merged into XLA_FLAGS as
this module's first executable statements (same idiom as
launch/dryrun.py). On sharded runs ``--check-parity`` asserts the
sharded end state is bit-identical to the vmap synchronous reference
(every pool leaf, counters included) — the shard_map-vs-vmap contract —
and falls through to the per-shard single-pool check when no migration
fired.
"""
import os
import sys

# --devices N must reach XLA before the backend initializes, and every
# repro import below pulls in jax: pre-scan argv, merge the flag first.
# (Mirrors common.sharding.force_host_device_count, inlined so nothing
# jax-adjacent is imported before the env var is set.)
for _i, _a in enumerate(sys.argv):
    _n = None
    if _a == "--devices" and _i + 1 < len(sys.argv):
        _n = sys.argv[_i + 1]
    elif _a.startswith("--devices="):
        _n = _a.split("=", 1)[1]
    if _n and _n.isdigit() and int(_n) > 1:
        _kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
        _kept.append(f"--xla_force_host_platform_device_count={_n}")
        os.environ["XLA_FLAGS"] = " ".join(_kept)

# (no `from __future__ import` — it would have to precede the XLA_FLAGS
# bootstrap above; same trade as launch/dryrun.py)
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.core.engine import batch as B
from repro.core.engine import state as S
from repro.core.engine.policy import POLICIES
from repro.fabric import Fabric, make_placement
from repro.simx import time as TM
from repro.simx.engine import TRAFFIC_KEYS, pool_cfg_for
from repro.simx.trace import WORKLOADS, make_rates_table, make_trace


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mcf", choices=sorted(WORKLOADS))
    ap.add_argument("--scheme", default="ibex", choices=sorted(POLICIES))
    ap.add_argument("--expanders", type=int, default=4)
    ap.add_argument("--placement", default="interleave",
                    choices=("interleave", "capacity", "locality"))
    ap.add_argument("--skew", type=float, default=0.0,
                    help="page share forced onto expander 0 (>0 overrides "
                         "--placement with a weighted interleave)")
    ap.add_argument("--accesses", type=int, default=4096)
    ap.add_argument("--pages", type=int, default=512)
    ap.add_argument("--prom", type=int, default=32,
                    help="promoted P-chunks per expander")
    ap.add_argument("--window", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--migration", default="spill",
                    choices=("spill", "rebalance", "off"),
                    help="MigrationPolicy: freelist-pressure spill, "
                         "pressure + traffic-imbalance rebalancing, or off")
    ap.add_argument("--no-spill", action="store_true",
                    help="back-compat alias for --migration off")
    ap.add_argument("--sync-migration", action="store_true",
                    help="force the synchronous reference driver (plan and "
                         "apply at every boundary, migration on the "
                         "critical path — the parity anchor)")
    ap.add_argument("--pipeline-depth", type=int, default=2, choices=(1, 2),
                    help="segment-scheduler depth: 2 overlaps migration "
                         "behind the next segment's replay, 1 degenerates "
                         "to the synchronous schedule")
    ap.add_argument("--verify-depth1", action="store_true",
                    help="replay the trace through the depth-1 pipeline AND "
                         "the synchronous driver and assert bit-identical "
                         "final state (the CI overlapped-migration smoke)")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="run the sharded driver on an N-device expander "
                         "mesh (forces N XLA host devices before backend "
                         "init via the argv pre-scan at module top; "
                         "requires --expanders divisible by N)")
    ap.add_argument("--check-parity", action="store_true")
    ap.add_argument("--trace", default=None, metavar="OUT.trace.json",
                    help="attach a repro.obs.Recorder (piggybacked on the "
                         "existing per-segment/per-epoch fetches — zero "
                         "extra syncs, asserted below), write the Perfetto "
                         "trace_event export there plus a metrics.json "
                         "sibling, and print the per-segment summary table")
    ap.add_argument("--device-profile", default="default",
                    help="comma-separated simx.time.DEVICE_PROFILES names "
                         f"({', '.join(sorted(TM.DEVICE_PROFILES))}) or "
                         "'calibrated' (engine constants from the measured "
                         "BENCH_kernels.json; paper constants if absent), "
                         "cycled across expanders — e.g. 'default,gen4' "
                         "makes an alternating mixed-generation fleet")
    args = ap.parse_args()
    enable_compile_cache()

    profiles = [p.strip() for p in args.device_profile.split(",") if p.strip()]
    unknown = [p for p in profiles
               if p != "calibrated" and p not in TM.DEVICE_PROFILES]
    if unknown:
        ap.error(f"unknown device profile(s) {unknown}; choose from "
                 f"{sorted(TM.DEVICE_PROFILES) + ['calibrated']}")
    if len(profiles) > args.expanders:
        ap.error(f"{len(profiles)} device profiles for "
                 f"{args.expanders} expanders")
    devices = [TM.calibrated_device() if p == "calibrated"
               else TM.DEVICE_PROFILES[p] for p in profiles]

    policy = POLICIES[args.scheme]
    cfg = pool_cfg_for(policy, n_pages=args.pages, n_pchunks=args.prom,
                       n_cchunks=2 * args.pages * 4)
    spec = WORKLOADS[args.workload]
    rates = make_rates_table(spec, args.pages, seed=args.seed)
    ospn, wr, blk = make_trace(spec, n_accesses=args.accesses,
                               n_pages=args.pages, seed=args.seed)
    n = args.expanders

    def new_placement():
        if args.skew > 0:
            rest = (1.0 - args.skew) / max(n - 1, 1)
            return make_placement("weighted", n, args.pages,
                                  weights=[args.skew] + [rest] * (n - 1))
        return make_placement(args.placement, n, args.pages)

    placement = new_placement()
    migration = "off" if args.no_spill else args.migration

    def make_fabric(pl, **kw):
        return Fabric(cfg, policy, pl, seed=args.seed,
                      rates_table=jnp.asarray(rates), window=args.window,
                      migration=migration, devices=devices, **kw)

    if args.devices is not None:
        from repro.fabric import shard as FS
        if jax.device_count() < args.devices:
            ap.error(f"--devices {args.devices} but only "
                     f"{jax.device_count()} XLA devices visible")
        owners = FS.device_of_expander(n, args.devices)
        kind = ("device(s)" if jax.devices()[0].platform == "tpu"
                else "forced host device(s)")
        print(f"mesh: {args.devices} {kind}, axis "
              f"'expander', {n} expanders "
              f"({n // args.devices} per device)")
        for d in range(args.devices):
            owned = np.nonzero(owners == d)[0]
            print(f"  device {d} ({jax.devices()[d].platform}): "
                  f"expanders {owned.tolist()}")

    rec = None
    if args.trace:
        from repro.obs import Recorder
        rec = Recorder()
    fab = make_fabric(placement, sync_migration=args.sync_migration,
                      pipeline_depth=args.pipeline_depth, obs=rec,
                      shard_devices=args.devices)
    t0 = time.time()
    fab.replay(ospn, wr, blk)
    dt = time.time() - t0
    agg = fab.counters()
    print(f"fabric: {n} expanders, placement="
          f"{'weighted' if args.skew > 0 else args.placement}, "
          f"profiles={','.join(profiles)}, "
          f"{args.accesses} accesses in {dt:.1f}s "
          f"({args.accesses / max(dt, 1e-9):,.0f} acc/s, compile included)")
    per = fab.counters_by_expander()
    delivered = fab.delivered_time()
    for e, c in enumerate(per):
        host = c["host_reads"] + c["host_writes"]
        internal = sum(c[k] for k in TRAFFIC_KEYS)
        print(f"  expander {e} ({profiles[e % len(profiles)]}): "
              f"host={host} internal={internal} "
              f"promotions={c['promotions']} "
              f"demotions={c['demotions_clean'] + c['demotions_dirty']} "
              f"delivered={delivered[e] * 1e6:.1f}us")
    print(f"  aggregate: host={agg['host_reads'] + agg['host_writes']} "
          f"internal={sum(agg[k] for k in TRAFFIC_KEYS)}")
    bottleneck = float(delivered.max())
    print(f"  delivered time (bottleneck expander "
          f"{int(delivered.argmax())}): {bottleneck * 1e6:.1f}us "
          f"({args.accesses / bottleneck:,.0f} modeled acc/s)")
    print(f"  migration ({fab.migration_policy.name}): {fab.spill_stats()}")
    ss = fab.sync_stats()
    if args.devices is not None:
        assert ss["segment_syncs"] == 0 and ss["epoch_syncs"] == 0, ss
        assert ss["boundary_syncs"] == ss["boundaries"], ss
        print(f"  syncs: {ss} (sharded: one fused fetch per boundary, "
              f"asserted)")
    else:
        assert ss["segment_syncs"] == ss["segments"], ss
        assert ss["epoch_syncs"] == ss["epochs"], ss
        print(f"  syncs: {ss} (one per segment + one per epoch, asserted)")
    pt = fab.pipeline_times()
    if pt is not None and fab.epochs_applied:
        over = float(np.max(pt["overlapped_s"]))
        sync = float(np.max(pt["sync_s"]))
        print(f"  pipeline pricing ({pt['mode']}): overlapped={over * 1e6:.1f}us "
              f"sync={sync * 1e6:.1f}us "
              f"(migration overlap hides {(sync - over) * 1e6:.2f}us)")

    if rec is not None:
        from repro.obs import export as OBX
        # the contract held with recording ON (sync asserts above); the
        # exported tracks must reconcile with the pipeline pricing exactly
        totals = OBX.fabric_track_totals(rec)
        if pt is not None:
            assert np.allclose(totals["overlapped_s"], pt["overlapped_s"],
                               rtol=1e-9), "trace drifted from pipeline_times"
        dev_totals = OBX.fabric_device_totals(rec)
        if dev_totals is not None:
            dts = fab.device_times()
            assert np.allclose(dev_totals["device_s"], dts["device_s"],
                               rtol=1e-9), \
                "device tracks drifted from Fabric.device_times"
            print(f"  device tracks: "
                  f"{[f'{t * 1e6:.1f}us' for t in dts['device_s']]} "
                  f"(reconcile with device_times at rtol=1e-9, asserted)")
        OBX.write_trace(rec, args.trace)
        mpath = (args.trace[: -len(".trace.json")] if
                 args.trace.endswith(".trace.json") else args.trace) \
            + ".metrics.json"
        OBX.write_metrics(rec, mpath, seed=args.seed)
        print(f"  trace: {args.trace} (+ {mpath}); per-expander track "
              f"totals reconcile with pipeline_times (asserted)")
        print(OBX.fabric_summary_table(rec))

    if args.verify_depth1:
        f1 = make_fabric(new_placement(), pipeline_depth=1)
        fs = make_fabric(new_placement(), sync_migration=True)
        f1.replay(ospn, wr, blk)
        fs.replay(ospn, wr, blk)
        assert f1.state_identical(fs), \
            "depth-1 pipeline drifted from the synchronous driver"
        print(f"  verify-depth1: depth-1 pipeline == synchronous driver "
              f"(bit-identical; {fs.epochs_applied} epochs)")

    if args.check_parity:
        if args.devices is not None:
            # the shard_map-vs-vmap contract: the sharded end state is
            # bit-identical (every pool leaf, counters included) to the
            # vmap synchronous reference on the same trace — migration
            # live included, since the collective apply replays the host
            # planner's exact move sequence
            ref = make_fabric(new_placement(), sync_migration=True)
            ref.replay(ospn, wr, blk)
            assert fab.state_identical(ref), \
                "sharded driver drifted from the vmap reference"
            print(f"parity: sharded (D={args.devices}) == vmap synchronous "
                  f"driver (bit-identical; {ref.epochs_applied} epochs; "
                  f"sharded used {ss['host_syncs']} host syncs vs "
                  f"{ref.sync_stats()['host_syncs']})")
        eids = placement.route(ospn)
        if (placement.overrides >= 0).any():
            print("parity check skipped: migration fired (re-run with "
                  "--migration off for the exact contract)")
            return
        stack0 = S.make_pool_stack(cfg, n, seed=args.seed,
                                   rates_table=jnp.asarray(rates))
        total = {k: 0 for k in S.COUNTER_NAMES}
        for e in range(n):
            sel = eids == e
            ref = B.replay_trace(S.pool_slice(stack0, e), cfg, policy,
                                 ospn[sel], wr[sel], blk[sel],
                                 window=args.window)
            for k, v in S.counters_dict(ref).items():
                total[k] += v
        assert fab.counters() == total, "fabric drifted from single-pool"
        print("parity: summed fabric counters == per-shard single-pool "
              "replays (exact)")


if __name__ == "__main__":
    main()
