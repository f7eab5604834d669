"""Workload trace generators for the paper-evaluation reproduction.

Each paper workload (Table 2) is modeled by four knobs measured from its
published behavior: memory intensity (accesses simulated), write ratio
(WPKI/RPKI), locality (Zipf exponent over the page footprint + streaming
fraction), and a page-content model (zero / 4-bit / 8-bit / raw block mix)
matching the compressibility the paper reports (Fig. 10: IBEX-1KB mean 1.59,
lbm & graphs poorly compressible, mcf/omnetpp highly compressible).

A trace is (ospn[i], is_write[i], block[i]) plus a per-page rates table
consumed by the payload-less pool (pool.rates_table).

Every generator is a deterministic function of its explicit ``seed`` — the
same seed the launchers take on the CLI (``launch/figs.py --seed``) and
the fabric derives its per-expander RNG streams from
(``engine.state.make_pool_stack``: ``fold_in(seed, expander)``); fabric
trace partitioning itself is a pure page-hash (fabric/placement.py). One
flag therefore reproduces a whole run bit-for-bit.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import numpy as np


@dataclass(frozen=True)
class WorkloadSpec:
    name: str
    wpki_ratio: float        # writes / (reads+writes)
    zipf_a: float            # locality: higher = hotter head
    stream_frac: float       # fraction of sequential-scan accesses
    footprint_pages: float   # footprint as a multiple of the promoted region
    zero_frac: float         # fraction of all-zero pages
    mix4: float              # fraction of 4-bit-compressible blocks
    mix8: float              # 8-bit; remainder raw


# Knobs derived from Table 2 RPKI/WPKI + Figs. 9-11 commentary.
WORKLOADS: Dict[str, WorkloadSpec] = {
    "bwaves":  WorkloadSpec("bwaves", 0.14, 0.9, 0.5, 0.8, 0.10, 0.55, 0.25),
    "mcf":     WorkloadSpec("mcf", 0.15, 0.8, 0.1, 2.5, 0.15, 0.60, 0.25),
    "parest":  WorkloadSpec("parest", 0.01, 1.1, 0.3, 0.6, 0.10, 0.55, 0.30),
    "lbm":     WorkloadSpec("lbm", 0.43, 0.7, 0.8, 1.2, 0.30, 0.10, 0.20),
    "omnetpp": WorkloadSpec("omnetpp", 0.32, 0.6, 0.1, 3.0, 0.10, 0.65, 0.25),
    "bfs":     WorkloadSpec("bfs", 0.06, 0.7, 0.3, 2.0, 0.25, 0.35, 0.30),
    "pr":      WorkloadSpec("pr", 0.02, 0.5, 0.2, 4.0, 0.10, 0.40, 0.35),
    "cc":      WorkloadSpec("cc", 0.10, 0.5, 0.2, 4.0, 0.10, 0.40, 0.35),
    "tc":      WorkloadSpec("tc", 0.41, 0.8, 0.3, 1.5, 0.25, 0.35, 0.30),
    "xsbench": WorkloadSpec("xsbench", 0.00, 0.6, 0.2, 2.5, 0.05, 0.45, 0.35),
}


def make_rates_table(spec: WorkloadSpec, n_pages: int, blocks: int = 4,
                     seed: int = 0) -> np.ndarray:
    """Per-page per-block rate codes (0 zero / 1 4-bit / 2 8-bit / 3 raw)."""
    rng = np.random.default_rng(seed)
    zero_page = rng.random(n_pages) < spec.zero_frac
    p_raw = max(0.0, 1.0 - spec.mix4 - spec.mix8)
    rates = rng.choice([1, 2, 3], size=(n_pages, blocks),
                       p=[spec.mix4, spec.mix8, p_raw])
    rates[zero_page] = 0
    # sprinkle zero blocks inside normal pages (stack/padding regions)
    zb = rng.random((n_pages, blocks)) < 0.08
    rates[zb & ~zero_page[:, None]] = 0
    return rates.astype(np.int32)


def make_trace(spec: WorkloadSpec, *, n_accesses: int, n_pages: int,
               seed: int = 0) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(ospn, is_write, block) arrays. Pages are random-placed (paper §5:
    random OS page allocation), so OSPNs carry no spatial locality."""
    rng = np.random.default_rng(seed + 1)
    n_stream = int(n_accesses * spec.stream_frac)
    n_zipf = n_accesses - n_stream
    # zipf over a randomly permuted page ranking
    ranks = rng.zipf(max(spec.zipf_a, 1.01) + 1e-9, size=2 * n_zipf)
    ranks = ranks[ranks <= n_pages][:n_zipf]
    while ranks.shape[0] < n_zipf:
        extra = rng.zipf(max(spec.zipf_a, 1.01))
        ranks = np.append(ranks, extra if extra <= n_pages else 1)
    perm = rng.permutation(n_pages)
    zipf_pages = perm[(ranks - 1).astype(np.int64)]
    # streaming scan wraps the footprint
    start = rng.integers(0, n_pages)
    stream_pages = perm[(start + np.arange(n_stream)) % n_pages]
    pages = np.concatenate([zipf_pages, stream_pages])
    order = rng.permutation(n_accesses)
    pages = pages[order]
    is_write = rng.random(n_accesses) < spec.wpki_ratio
    block = rng.integers(0, 4, size=n_accesses)
    return (pages.astype(np.int32), is_write.astype(bool),
            block.astype(np.int32))


def write_instrumented_trace(base: WorkloadSpec, write_ratio: float,
                             **kw) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fig. 16: re-instrument a read-only workload with binomial writes."""
    spec = WorkloadSpec(base.name, write_ratio, base.zipf_a, base.stream_frac,
                        base.footprint_pages, base.zero_frac, base.mix4,
                        base.mix8)
    return make_trace(spec, **kw)
