"""Paper-figure reproduction: one function per reproduced figure
(DESIGN.md §7 index), each a sweep of ``simx.engine.run_workload`` cells
priced by the ``simx.time`` delivered-time model.

  PYTHONPATH=src python -m repro.launch.figs                 # quick sweep
  PYTHONPATH=src python -m repro.launch.figs --full          # every workload
  PYTHONPATH=src python -m repro.launch.figs --only fig09,fig14 --seed 0

Prints ``name,derived`` CSV rows. Quick mode trims workloads and access
counts so the whole set stays CPU-tractable; ``--seed`` seeds every trace,
rates table and pool, so a run is reproducible bit for bit.
"""
from __future__ import annotations

import argparse
from typing import Dict, List

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.types import replace
from repro.simx import time as TM
from repro.simx.engine import run_workload
from repro.simx.trace import WORKLOADS, WorkloadSpec, make_trace

QUICK_WL = ["mcf", "lbm", "omnetpp", "pr", "xsbench"]
FULL_WL = list(WORKLOADS)
N_Q, N_F = 4000, 12000
PROM_Q, PROM_F = 64, 96


def _wl(quick: bool) -> List[str]:
    return QUICK_WL if quick else FULL_WL


def _n(quick: bool) -> int:
    return N_Q if quick else N_F


def _prom(quick: bool) -> int:
    return PROM_Q if quick else PROM_F


def _cell(scheme: str, spec: WorkloadSpec, quick: bool, seed: int,
          **kw) -> Dict[str, float]:
    return run_workload(scheme, spec, n_accesses=_n(quick),
                        promoted_pages=_prom(quick), seed=seed, **kw)


def fig01_bandwidth(quick: bool, seed: int) -> List[Dict]:
    """Fig. 1: dual-channel vs ideal internal bandwidth (block compression)."""
    rows = []
    for wl in _wl(quick):
        real = _cell("ibex_base", WORKLOADS[wl], quick, seed)
        ideal = _cell("ibex_base", WORKLOADS[wl], quick, seed,
                      device=TM.ideal_bandwidth(TM.DeviceConfig()))
        rows.append({"name": f"fig01.{wl}",
                     "derived": f"limited/ideal="
                                f"{real['time_s'] / ideal['time_s']:.3f}"})
    return rows


def fig09_speedup(quick: bool, seed: int) -> List[Dict]:
    """Fig. 9: normalized perf per scheme; headline IBEX-vs-TMCC/DyLeCT."""
    schemes = ["ibex", "tmcc", "dylect", "mxt", "dmc", "compresso"]
    perf: Dict[str, Dict[str, float]] = {s: {} for s in schemes}
    rows = []
    for s in schemes:
        for wl in _wl(quick):
            r = _cell(s, WORKLOADS[wl], quick, seed)
            perf[s][wl] = r["normalized_perf"]
            rows.append({"name": f"fig09.{s}.{wl}",
                         "derived": f"norm_perf={r['normalized_perf']:.3f}"})
    gm = {s: float(np.exp(np.mean(np.log(
        [max(v, 1e-9) for v in perf[s].values()])))) for s in schemes}
    for other in ("tmcc", "dylect", "mxt", "dmc"):
        rows.append({"name": f"fig09.speedup_ibex_over_{other}",
                     "derived": f"x{gm['ibex'] / gm[other]:.2f}"})
    return rows


def fig10_ratio(quick: bool, seed: int) -> List[Dict]:
    """Fig. 10: compression ratios (IBEX-1KB, IBEX-4KB, MXT, Compresso)."""
    rows = []
    for name, scheme in (("ibex_1kb", "ibex"), ("ibex_4kb", "ibex_base"),
                         ("mxt", "mxt"), ("compresso", "compresso")):
        ratios = [max(_cell(scheme, WORKLOADS[wl], quick,
                            seed)["compression_ratio"], 1e-3)
                  for wl in _wl(quick)]
        gm = float(np.exp(np.mean(np.log(ratios))))
        rows.append({"name": f"fig10.{name}", "derived": f"ratio={gm:.2f}"})
    return rows


def fig11_breakdown(quick: bool, seed: int) -> List[Dict]:
    """Fig. 11: per-class traffic, IBEX normalized to TMCC."""
    rows = []
    tot_i = tot_t = 0.0
    for wl in _wl(quick):
        ib = _cell("ibex", WORKLOADS[wl], quick, seed)
        tm = _cell("tmcc", WORKLOADS[wl], quick, seed)
        tot_i += ib["internal_accesses"]
        tot_t += tm["internal_accesses"]
        rel = ib["internal_accesses"] / max(tm["internal_accesses"], 1)
        clean = ib["demotions_clean"] / max(
            ib["demotions_clean"] + ib["demotions_dirty"], 1)
        rows.append({"name": f"fig11.{wl}",
                     "derived": f"ibex/tmcc={rel:.3f};clean_frac={clean:.2f}"})
    rows.append({"name": "fig11.total_traffic_reduction",
                 "derived": f"{1 - tot_i / max(tot_t, 1):.1%}"})
    return rows


def fig12_background(quick: bool, seed: int) -> List[Dict]:
    """Fig. 12: practical vs miracle (no activity/scan traffic)."""
    rows = []
    for wl in _wl(quick):
        r = _cell("ibex", WORKLOADS[wl], quick, seed)
        t = {k: r[k] for k in ("host_reads", "host_writes", "zero_served",
                               "promotions", "demotions_dirty",
                               "recompress_retry")}
        t["internal_accesses"] = (r["internal_accesses"] - r["activity_rd"]
                                  - r["activity_wr"])
        tm = TM.exec_time_dict(t, TM.DeviceConfig())
        rows.append({"name": f"fig12.{wl}",
                     "derived": f"practical/miracle={r['time_s'] / tm:.3f}"})
    return rows


def fig13_ablation(quick: bool, seed: int) -> List[Dict]:
    """Fig. 13: traffic as S, C, M are applied incrementally."""
    rows = []
    for wl in (_wl(quick)[:3] if quick else _wl(quick)):
        base, s, sc, scm = (_cell(scheme, WORKLOADS[wl], quick, seed)
                            for scheme in ("ibex_base", "ibex_s", "ibex_sc",
                                           "ibex_scm"))
        b = max(base["internal_accesses"], 1)
        rows.append({
            "name": f"fig13.{wl}",
            "derived": (f"S={s['internal_accesses'] / b:.3f};"
                        f"SC={sc['internal_accesses'] / b:.3f};"
                        f"SCM={scm['internal_accesses'] / b:.3f}")})
    return rows


def _device_sweep(r: Dict[str, float], devices) -> np.ndarray:
    """Normalized perf of one cell's traffic under a stacked device sweep:
    one replay, every device point priced in a single vectorized
    ``exec_time_vec`` call (traffic does not depend on the device model)."""
    lanes = TM.stack_devices(devices, xp=np)
    vec = TM.counters_from_dict(r)
    times = TM.exec_time_vec(
        np.broadcast_to(vec, (len(devices),) + vec.shape), lanes)
    host = r["host_reads"] + r["host_writes"]
    base = TM.uncompressed_time(np.full((len(devices),), host), lanes)
    return base / times


def fig14_latency(quick: bool, seed: int) -> List[Dict]:
    """Fig. 14: sensitivity to CXL round-trip latency (vectorized sweep)."""
    r = _cell("ibex", WORKLOADS["pr"], quick, seed)
    lats = (70e-9, 150e-9, 250e-9, 400e-9)
    norm = _device_sweep(r, [replace(TM.DeviceConfig(), cxl_lat=lat)
                             for lat in lats])
    return [{"name": f"fig14.cxl_{int(lat * 1e9)}ns",
             "derived": f"norm_perf={norm[i]:.3f}"}
            for i, lat in enumerate(lats)]


def fig15_decomp(quick: bool, seed: int) -> List[Dict]:
    """Fig. 15: sensitivity to decompression cycles (robustness claim;
    vectorized sweep)."""
    r = _cell("ibex", WORKLOADS["mcf"], quick, seed)
    cycs = (64, 128, 256, 512)
    norm = _device_sweep(r, [replace(TM.DeviceConfig(), decomp_cycles=cyc)
                             for cyc in cycs])
    rows = [{"name": f"fig15.decomp_{cyc}cyc",
             "derived": f"norm_perf={norm[i]:.3f}"}
            for i, cyc in enumerate(cycs)]
    drop = 1 - norm[-1] / max(norm[0], 1e-9)
    rows.append({"name": "fig15.total_drop", "derived": f"{drop:.1%}"})
    return rows


def fig16_write(quick: bool, seed: int) -> List[Dict]:
    """Fig. 16: write-intensity sweep on the read-only workload (XSBench)."""
    rows = []
    base = None
    for ratio in (0.0, 1 / 6, 1 / 3, 0.5, 2 / 3, 5 / 6):
        spec = replace(WORKLOADS["xsbench"], wpki_ratio=ratio)
        r = _cell("ibex", spec, quick, seed)
        if base is None:
            base = r["time_s"]
        rows.append({"name": f"fig16.rw_{ratio:.2f}",
                     "derived": f"slowdown={r['time_s'] / base:.3f}"})
    return rows


def fig17_fault(quick: bool, seed: int) -> List[Dict]:
    """Fig. 17: page-fault reduction under 50%-of-working-set memory, using
    each workload's measured compression ratio as the capacity multiplier
    (LRU residency)."""
    rows = []
    n_pages = 512
    for wl in _wl(quick):
        ratio = max(_cell("ibex", WORKLOADS[wl], quick,
                          seed)["compression_ratio"], 1.0)
        pages, _, _ = make_trace(WORKLOADS[wl], n_accesses=_n(quick),
                                 n_pages=n_pages, seed=seed)
        faults = {}
        for label, cap in (("base", n_pages // 2),
                           ("ibex", min(int(n_pages // 2 * ratio), n_pages))):
            resident: dict = {}
            n = 0
            for t, p in enumerate(pages):
                if p in resident:
                    resident[p] = t
                    continue
                n += 1
                if len(resident) >= cap:
                    del resident[min(resident, key=resident.get)]
                resident[p] = t
            faults[label] = n
        red = 1 - faults["ibex"] / max(faults["base"], 1)
        rows.append({"name": f"fig17.{wl}",
                     "derived": f"fault_reduction={red:.1%}"})
    return rows


ALL_FIGS = [fig01_bandwidth, fig09_speedup, fig10_ratio, fig11_breakdown,
            fig12_background, fig13_ablation, fig14_latency, fig15_decomp,
            fig16_write, fig17_fault]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="every workload at full access counts")
    ap.add_argument("--only", default="",
                    help="comma-separated figure prefixes, e.g. fig09,fig14")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    figs = [f for f in ALL_FIGS
            if not only or any(f.__name__.startswith(o) for o in only)]
    if not figs:
        ap.error(f"--only {args.only!r} matches no figure; choose from "
                 f"{[f.__name__ for f in ALL_FIGS]}")
    enable_compile_cache()
    print("name,derived")
    for fig in figs:
        for row in fig(not args.full, args.seed):
            print(f"{row['name']},{row['derived']}", flush=True)


if __name__ == "__main__":
    main()
