"""Pool state: the four device-memory regions + counters (DESIGN.md §3).

Functional state machine over:
  * ``p_store``  — promoted region (uncompressed P-chunks, 4KB)
  * ``c_store``  — compressed region (512B C-chunks; an aligned-group tail
                   sub-region serves incompressible pages behind one pointer)
  * ``meta``     — 32B compacted metadata entries (metadata.py)
  * ``activity`` — 4B page-activity entries + clock hand (activity.py)

plus the metadata-cache model that drives lazy reference updates, and traffic
counters in 64B-access units (the paper's measurement unit).

State-machine invariants (enforced by tests/test_pool_properties.py,
DESIGN.md §9):
  I1  every C-chunk is free XOR referenced by exactly one page
  I2  promoted(page) <=> P-chunk allocated <=> activity entry allocated
  I3  dirty <=> num_chunks == 0 for promoted pages (no compressed copy)
  I4  clean promoted pages have shadow_valid=1 and intact chunks (§4.5)
  I5  read-your-writes at block granularity
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.common.types import PoolConfig
from repro.core import freelist as fl
from repro.core import mcache as mcc
from repro.core import metadata as md

# ---------------------------------------------------------------------------
# Traffic counters (64B-access units unless noted).
# ---------------------------------------------------------------------------
C_META_RD, C_META_WR, C_DATA_RD, C_DATA_WR, C_PROMO_RD, C_PROMO_WR, \
    C_DEMO_RD, C_DEMO_WR, C_ACT_RD, C_ACT_WR, C_ZERO_SERVED, C_RANDOM_FB, \
    C_DEMO_CLEAN, C_DEMO_DIRTY, C_PROMOTIONS, C_HOST_RD, C_HOST_WR, \
    C_MC_HIT, C_MC_MISS, C_RECOMP_RETRY, NUM_COUNTERS = range(21)

CTR_DTYPE = jnp.int32  # 64B-access counts; int32 suffices at test/sim scale

COUNTER_NAMES = [
    "metadata_rd", "metadata_wr", "data_rd", "data_wr", "promo_rd", "promo_wr",
    "demo_rd", "demo_wr", "activity_rd", "activity_wr", "zero_served",
    "random_fallback", "demotions_clean", "demotions_dirty", "promotions",
    "host_reads", "host_writes", "mcache_hits", "mcache_misses",
    "recompress_retry",
]

# The ten *internal 64B-access* categories (excludes host accesses and event
# counters) — the canonical definition of "internal traffic" shared by the
# metrics here, simx.engine.TRAFFIC_KEYS, and the simx.time delivered-time
# model, so the counter layout and the model can never drift on key names.
TRAFFIC_IDX = (C_META_RD, C_META_WR, C_DATA_RD, C_DATA_WR, C_PROMO_RD,
               C_PROMO_WR, C_DEMO_RD, C_DEMO_WR, C_ACT_RD, C_ACT_WR)
TRAFFIC_NAMES = tuple(COUNTER_NAMES[i] for i in TRAFFIC_IDX)


class Pool(NamedTuple):
    meta: jnp.ndarray        # uint32[n_pages, 8]
    activity: jnp.ndarray    # uint32[n_pchunks]
    hand: jnp.ndarray        # int32[]
    cfree: fl.FreeList       # single C-chunks
    gfree: fl.FreeList       # aligned 8-chunk groups (values = base chunk idx)
    pfree: fl.FreeList       # P-chunks
    cache: mcc.MCache
    counters: jnp.ndarray    # int32[NUM_COUNTERS]
    rng: jnp.ndarray
    c_store: jnp.ndarray     # uint8[n_chunks_total, chunk_bytes] (or [0, _])
    p_store: jnp.ndarray     # uint8[n_pchunks, page_bytes]       (or [0, _])
    rates_table: jnp.ndarray  # int32[n_pages, 4] content model — used instead
    #                           of encode_page when store_payload=False (simx)


def n_single_chunks(cfg: PoolConfig) -> int:
    """Compressed region split: 7/8 singles, 1/8 aligned groups (static)."""
    return (cfg.n_cchunks * 7 // 8) // 8 * 8


def make_pool(cfg: PoolConfig, seed: int = 0,
              rates_table: jnp.ndarray | None = None) -> Pool:
    n_single = n_single_chunks(cfg)
    n_groups = (cfg.n_cchunks - n_single) // 8
    gbases = jnp.asarray(n_single, jnp.int32) + 8 * jnp.arange(n_groups, dtype=jnp.int32)
    pay_c = cfg.n_cchunks if cfg.store_payload else 0
    pay_p = cfg.n_pchunks if cfg.store_payload else 0
    if rates_table is None:
        rates_table = jnp.zeros((cfg.n_pages, cfg.blocks_per_page), jnp.int32)
    return Pool(
        meta=md.empty_table(cfg.n_pages),
        activity=jnp.zeros((cfg.n_pchunks,), jnp.uint32),
        hand=jnp.asarray(0, jnp.int32),
        cfree=fl.make_freelist(n_single),
        gfree=fl.FreeList(items=gbases, top=jnp.asarray(n_groups, jnp.int32)),
        pfree=fl.make_freelist(cfg.n_pchunks),
        cache=mcc.make_mcache(cfg.mcache_sets, cfg.mcache_ways),
        counters=jnp.zeros((NUM_COUNTERS,), CTR_DTYPE),
        rng=jax.random.PRNGKey(seed),
        c_store=jnp.zeros((pay_c, cfg.chunk_bytes), jnp.uint8),
        p_store=jnp.zeros((pay_p, cfg.page_bytes), jnp.uint8),
        rates_table=jnp.asarray(rates_table, jnp.int32),
    )


def bump(counters: jnp.ndarray, idx: int, n=1) -> jnp.ndarray:
    return counters.at[idx].add(jnp.asarray(n, CTR_DTYPE))


# ---------------------------------------------------------------------------
# Stacked pools (multi-expander fabric, repro.fabric): N independent pools as
# one pytree whose every leaf carries a leading expander axis, advanced in
# parallel with jax.vmap.
# ---------------------------------------------------------------------------

def make_pool_stack(cfg: PoolConfig, n_expanders: int, seed: int = 0,
                    rates_table: jnp.ndarray | None = None) -> Pool:
    """N identically-configured pools stacked leaf-wise. Every expander gets
    its own RNG stream derived from ``seed`` (fold_in by expander index), so
    a fabric run is bit-reproducible from one CLI seed and expanders never
    share randomness. The OSPA page space (and content model) is the full
    ``cfg.n_pages`` on every expander — placement decides which pages a
    given expander ever sees (fabric/placement.py)."""
    base = make_pool(cfg, seed=seed, rates_table=rates_table)
    stacked = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (n_expanders,) + a.shape), base)
    keys = jax.vmap(lambda e: jax.random.fold_in(
        jax.random.PRNGKey(seed), e))(jnp.arange(n_expanders))
    return stacked._replace(rng=keys)


def pool_slice(stack: Pool, e: int) -> Pool:
    """Expander ``e``'s pool out of a stacked state (host-side: spill
    orchestration, invariant checks)."""
    return jax.tree_util.tree_map(lambda a: a[e], stack)


def pool_unslice(stack: Pool, e: int, pool: Pool) -> Pool:
    """Write one expander's pool back into the stacked state."""
    return jax.tree_util.tree_map(lambda s, a: s.at[e].set(a), stack, pool)


def stacked_counters(stack: Pool) -> jnp.ndarray:
    """Summed counters across expanders: int32[NUM_COUNTERS]."""
    return jnp.sum(stack.counters, axis=0)


def stacked_counters_dict(stack: Pool) -> dict:
    """Aggregate counters of a stacked pool state, same keys as
    ``counters_dict`` — per-expander traffic sums are the fabric's parity
    contract with single-pool replay (tests/test_fabric.py)."""
    vals = [int(v) for v in stacked_counters(stack)]
    return dict(zip(COUNTER_NAMES, vals))


def per_expander_counters(stack: Pool) -> list:
    """One ``counters_dict`` per expander, in expander order."""
    arr = [[int(v) for v in row] for row in stack.counters]
    return [dict(zip(COUNTER_NAMES, row)) for row in arr]


# ---------------------------------------------------------------------------
# Metrics.
# ---------------------------------------------------------------------------

def compression_ratio(pool: Pool, cfg: PoolConfig) -> jnp.ndarray:
    """Effective ratio = logical bytes of valid pages / physical bytes used
    (chunks + promoted duplicates, i.e. shadowing costs what the paper says)."""
    valid = md.get_valid(pool.meta[:, 0]) == 1
    logical = jnp.sum(valid) * cfg.page_bytes
    n_single = n_single_chunks(cfg)
    n_groups = (cfg.n_cchunks - n_single) // 8
    used_chunks = (n_single - fl.free_count(pool.cfree)) + \
        8 * (n_groups - fl.free_count(pool.gfree))
    used_p = cfg.n_pchunks - fl.free_count(pool.pfree)
    physical = used_chunks * cfg.chunk_bytes + used_p * cfg.page_bytes
    return logical / jnp.maximum(physical, 1)


def counters_dict(pool: Pool) -> dict:
    vals = [int(v) for v in pool.counters]
    return dict(zip(COUNTER_NAMES, vals))


def traffic_vector(counters) -> jnp.ndarray:
    """Internal-traffic view of a counter vector: ``[..., NUM_COUNTERS]`` →
    ``[..., len(TRAFFIC_IDX)]`` in ``TRAFFIC_IDX`` order. Works on numpy
    and jnp arrays (inside jit/vmap), with any leading batch/expander axes
    — the array-native hook the delivered-time model (simx/time.py) and
    the fabric's per-segment accounting consume."""
    return counters[..., list(TRAFFIC_IDX)]


def counters_snapshot(pool: Pool) -> jnp.ndarray:
    """A point-in-time counter vector. Pool state is immutable, so the
    live array IS the snapshot; this names the intent at segment
    boundaries (fabric per-segment deltas)."""
    return pool.counters


def counters_delta(before: jnp.ndarray, after: jnp.ndarray) -> jnp.ndarray:
    """Per-segment counter delta between two snapshots (leading axes — e.g.
    the expander axis of a stacked pool — broadcast through). The hook
    that per-segment delivered-time accounting, async-migration overlap and
    traffic-imbalance rebalancing (ROADMAP) are built on."""
    return after - before


def counters_delta_dict(delta) -> dict:
    """Name-keyed view of a counter delta: ``[..., NUM_COUNTERS]`` (leading
    axes — e.g. the expander axis — summed) → ``{counter_name: int}``. The
    layout-safe way host-side consumers (the repro.obs telemetry drains,
    summary tables) read fetched deltas: keys come from ``COUNTER_NAMES``,
    never integer positions, so the R3 drift rule holds by construction.
    Accepts numpy or (host) jnp arrays."""
    vals = delta.reshape(-1, NUM_COUNTERS).sum(axis=0)
    return {k: int(v) for k, v in zip(COUNTER_NAMES, vals)}


def total_traffic(pool: Pool) -> jnp.ndarray:
    """Total internal 64B accesses (excludes host_reads/host_writes and
    event counters)."""
    return jnp.sum(traffic_vector(pool.counters), axis=-1)
