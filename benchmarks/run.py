"""Benchmark harness entry point: one function per paper table/figure plus
kernel/system micro-benchmarks. Prints ``name,us_per_call,derived`` CSV.

Usage:
  PYTHONPATH=src python -m benchmarks.run           # quick mode (CI-sized)
  PYTHONPATH=src python -m benchmarks.run --full    # full workload sweep
  PYTHONPATH=src python -m benchmarks.run --only fig09,kernel
"""
from __future__ import annotations

import argparse
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--seed", type=int, default=0,
                    help="one seed, threaded end-to-end (trace generation, "
                         "per-expander RNG streams, model params) so every "
                         "BENCH_*.json run is bit-reproducible")
    args = ap.parse_args()
    quick = not args.full
    only = [s.strip() for s in args.only.split(",") if s.strip()]

    # the fabric suite sweeps the sharded fabric over D in {1, 2, 4, 8}; on
    # the CPU that needs eight host devices, forced before jax initializes
    # its backend (the flag reaches only the CPU platform, so a TPU host
    # still sweeps its own chips)
    if "xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        from repro.common.sharding import force_host_device_count
        force_host_device_count(8)

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fabric_bench, kernel_bench, lint_bench,
                            paper_figs, serve_bench, simx_bench,
                            system_bench)

    suites = [(f.__name__, lambda q, s, f=f: f(q)) for f in
              paper_figs.ALL_FIGS]
    # Pallas kernel timings + engine calibration -> BENCH_kernels.json
    suites.append(("kernels", kernel_bench.run))
    suites.append(("system", lambda q, s: system_bench.run(q)))
    # trace-replay throughput; also writes BENCH_simx.json (accesses/sec per
    # scheme, serial-vs-batched) so the perf trajectory is machine-readable
    suites.append(("simx", simx_bench.run))
    # serving engine: per-lane baseline vs batched scheduler -> BENCH_serve.json
    suites.append(("serve", serve_bench.run))
    # multi-expander fabric: 1/2/4/8 scaling + skew + parity -> BENCH_fabric.json
    suites.append(("fabric", fabric_bench.run))
    # jit-hygiene lint over src vs committed baseline -> BENCH_lint.json;
    # runs LAST so its meta.lint stamp lands in every BENCH_*.json above
    suites.append(("lint", lint_bench.run))

    print("name,us_per_call,derived")
    failed = 0
    for name, fn in suites:
        if only and not any(name.startswith(o) or o in name for o in only):
            continue
        try:
            for row in fn(quick, args.seed):
                print(f"{row['name']},{row['us']:.1f},{row['derived']}",
                      flush=True)
        except Exception as e:  # keep the suite running; count failures
            failed += 1
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
