"""Trace-driven evaluation engine: runs a workload trace through the pool
engine (payload-less) under each compared scheme, reproducing the paper's
SST-based methodology as traffic counts + the simx.time delivered-time
model.

Schemes are first-class ``Policy`` modules (repro.core.engine.policy): each
scheme's extra traffic — TMCC's LRU-list updates and zsmalloc bookkeeping,
DyLeCT's dual-table probes, MXT's on-chip tags, DMC's 8x migration — is
charged by policy hooks at the access site where it physically occurs; there
are no post-hoc counter adjustments. Traces replay through the batched
front-end (repro.core.engine.batch): a window of W accesses per scan step
with vectorized fast-path accounting, which is what makes the full workload
sweep CPU-tractable.

Compresso (line-level, no promotion machinery) keeps its dedicated model.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import PoolConfig, replace
from repro.core.engine import batch as B
from repro.core.engine import state as S
from repro.core.engine.policy import POLICIES, Policy
from repro.simx import time as TM
from repro.simx.trace import WorkloadSpec, make_rates_table, make_trace

# name -> Policy; the per-scheme behavior lives in repro.core.engine.policy
SCHEMES: Dict[str, Policy] = POLICIES

# the ten internal-traffic categories, derived from the counter layout so
# the metrics dicts and the delivered-time model share one key set
TRAFFIC_KEYS = S.TRAFFIC_NAMES

DEFAULT_WINDOW = B.DEFAULT_WINDOW


def pool_cfg_for(policy: Policy, *, n_pages: int, n_pchunks: int,
                 n_cchunks: int) -> PoolConfig:
    return PoolConfig(
        # mcache MUST be much smaller than the page population (paper:
        # 24MB cache footprint vs GBs of pages) — an oversized cache makes
        # every page probe-hit and forces the clock into pure random
        # fallback, inverting the mechanism being measured
        n_pages=n_pages, n_cchunks=n_cchunks, n_pchunks=n_pchunks,
        mcache_sets=4, mcache_ways=8, demote_watermark=8,
        shadow=policy.shadow, coloc=policy.coloc, compact=policy.compact,
        zero_elision=policy.zero_elision, store_payload=False)


def first_touch_populate(pool, cfg: PoolConfig, policy: Policy, *,
                         n_used: int, seed: int = 0,
                         window: int = DEFAULT_WINDOW):
    """Write every used page once (first touch -> promoted; demotes), then
    zero the counters. Padded to ``cfg.n_pages`` accesses (cycling) so the
    replay length is static per scheme. Shared by run_workload, the replay
    benchmark, and the parity tests so all warm pools identically."""
    order = np.random.default_rng(seed).permutation(n_used).astype(np.int32)
    order = order[np.arange(cfg.n_pages) % n_used]
    pool = B.replay_trace(pool, cfg, policy, order,
                          np.ones((cfg.n_pages,), bool),
                          np.zeros((cfg.n_pages,), np.int32), window=window)
    return pool._replace(counters=jnp.zeros_like(pool.counters))


def run_workload(scheme_name: str, spec: WorkloadSpec, *,
                 n_accesses: int = 20000, promoted_pages: int = 128,
                 seed: int = 0, first_touch: bool = True,
                 device: Optional[TM.DeviceConfig] = None,
                 window: int = DEFAULT_WINDOW, obs=None) -> Dict[str, float]:
    """Run one (scheme x workload) cell; returns traffic + time metrics.

    Pool dimensions are FIXED (4x promoted region) across workloads so the
    jitted replay compiles once per scheme; a workload's footprint is
    realized by restricting which pages its trace touches. ``window=1``
    forces the serial one-access-per-step scan (benchmark baseline).
    ``obs`` (a ``repro.obs.Recorder``) records the finished cell's metrics
    — host data the run already produced, zero extra syncs."""
    policy = SCHEMES[scheme_name]
    n_pages = 4 * promoted_pages
    n_used = min(max(int(promoted_pages * spec.footprint_pages), 32), n_pages)
    rates = make_rates_table(spec, n_pages, seed=seed)
    ospn, is_write, block = make_trace(spec, n_accesses=n_accesses,
                                       n_pages=n_used, seed=seed)
    dev = device or TM.DeviceConfig()
    if policy.block4k_engine:
        dev = replace(dev, block_scale=4.0)

    if policy.line_level:
        out = _run_compresso(spec, rates[:n_used], ospn, is_write, dev)
    else:
        cfg = pool_cfg_for(policy, n_pages=n_pages,
                           n_pchunks=promoted_pages,
                           n_cchunks=2 * n_pages * 8)
        pool = S.make_pool(cfg, seed=seed, rates_table=jnp.asarray(rates))
        if first_touch:
            pool = first_touch_populate(pool, cfg, policy, n_used=n_used,
                                        seed=seed, window=window)
        pool = B.replay_trace(pool, cfg, policy, ospn, is_write, block,
                              window=window)
        c = S.counters_dict(pool)
        out = _finalize(c, dev,
                        ratio=float(S.compression_ratio(pool, cfg)))
    if obs is not None:
        obs.record_cell(scheme_name, spec.name, out)
    return out


def _finalize(c: Dict[str, int], dev: TM.DeviceConfig, ratio: float
              ) -> Dict[str, float]:
    """Assemble the metrics dict. All scheme-specific traffic was already
    counted in place by policy hooks — nothing is adjusted here. Time comes
    from the vectorized model over the counter vector (float64 host path —
    bitwise what ``time.exec_time_dict`` computes)."""
    t = {k: float(c[k]) for k in TRAFFIC_KEYS}
    internal = sum(t.values())
    traffic = dict(t, internal_accesses=internal,
                   host_reads=c["host_reads"], host_writes=c["host_writes"],
                   zero_served=c["zero_served"],
                   promotions=c["promotions"],
                   demotions_clean=c["demotions_clean"],
                   demotions_dirty=c["demotions_dirty"],
                   recompress_retry=c.get("recompress_retry", 0),
                   random_fallback=c["random_fallback"],
                   mcache_hits=c["mcache_hits"],
                   mcache_misses=c["mcache_misses"])
    host = c["host_reads"] + c["host_writes"]
    time_s = float(TM.exec_time_vec(TM.counters_from_dict(traffic), dev))
    base_s = TM.uncompressed_time(host, dev)
    return dict(traffic, time_s=time_s, uncompressed_s=base_s,
                normalized_perf=base_s / time_s, compression_ratio=ratio)


def _run_compresso(spec: WorkloadSpec, rates: np.ndarray, ospn: np.ndarray,
                   is_write: np.ndarray, dev: TM.DeviceConfig
                   ) -> Dict[str, float]:
    """Line-level compression (no promotion machinery): metadata access on
    mcache miss; ~1.05 data accesses per read (lines pack across 64B), ~2.2
    per write (read-modify-write + occasional size-overflow repack)."""
    from repro.core import mcache as MC
    mc = MC.make_mcache(32, 16)

    @jax.jit
    def run(mc, pages):
        def step(carry, p):
            mc, h = carry
            mc, hit, _ = MC.access(mc, p)
            return (mc, h + hit.astype(jnp.int32)), None
        (mc, h), _ = jax.lax.scan(step, (mc, jnp.int32(0)), pages)
        return h
    hits = int(run(mc, jnp.asarray(ospn, jnp.int32)))
    n = len(ospn)
    misses = n - hits
    reads = int((~is_write).sum())
    writes = int(is_write.sum())
    t = {k: 0.0 for k in TRAFFIC_KEYS}
    t["metadata_rd"] = float(misses)
    t["metadata_wr"] = float(writes * 0.1)    # size-class changes
    t["data_rd"] = reads * 1.05
    t["data_wr"] = writes * 2.2
    internal = sum(t.values())
    # line-level ratio: a 64B window only captures the *strong* patterns
    # (zero lines + narrow-range data == our rate<=1 blocks) at ~2:1; the
    # 8-bit-class blocks that a 1KB dictionary still compresses are
    # incompressible at line granularity (paper: Compresso mean 1.24,
    # far below block-level)
    comp_frac = float((rates <= 1).mean())
    ratio = 1.0 / (comp_frac * 0.55 + (1 - comp_frac) * 1.0)
    traffic = dict(t, internal_accesses=internal, host_reads=reads,
                   host_writes=writes, zero_served=0, promotions=0,
                   demotions_clean=0, demotions_dirty=0, recompress_retry=0,
                   random_fallback=0, mcache_hits=hits, mcache_misses=misses)
    time_s = float(TM.exec_time_vec(TM.counters_from_dict(traffic), dev))
    base_s = TM.uncompressed_time(n, dev)
    return dict(traffic, time_s=time_s, uncompressed_s=base_s,
                normalized_perf=base_s / time_s, compression_ratio=ratio)
