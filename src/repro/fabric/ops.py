"""Cross-expander migration mechanism (DESIGN.md §11/§13).

When pages move between expanders — freelist-pressure spill or
traffic-imbalance rebalancing (fabric/migration.py decides) — the
mechanism is the same: the page's chunks are read on the source (charged
as demotion-read traffic there), freed, and the page is re-stored on the
destination (allocation + demotion-write + compression-store bookkeeping
charged there) — the same §4 mechanism ops demotion uses, so invariants
I1–I5 hold on both expanders after every migration. Only *non-promoted*
chunk-backed pages are eligible: hot pages stay where their traffic is,
and zero pages occupy no chunks so moving them frees nothing.

The plan/apply split (§13): ``segment_stats`` computes the per-expander
facts a ``MigrationPolicy`` plans from — freelist headroom, per-page
eligibility, per-page referenced bits (metadata-cache residency, the
§4.4 lazy-reference live set) — *inside* the vmapped segment replay, so
planning costs no extra host sync. ``apply_migrations`` applies one
epoch's explicit (page, src, dst) moves on the stacked pool state in a
single jit call, re-checking eligibility and donor headroom per move —
a page that promoted or invalidated while its plan was in flight is
skipped, never corrupted. ``spill_pages`` (in-jit candidate selection on
a sliced pool pair) is the PR 3 API, kept for compatibility.

Traffic is charged per expander on the pool the access physically
touches; fabric-level event counts (pages/bytes moved, epochs, syncs)
live on the host ``Fabric`` object (fabric/replay.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import PoolConfig
from repro.core import mcache as mcc
from repro.core import metadata as md
from repro.core.engine import ops
from repro.core.engine.policy import Policy
from repro.core.engine.state import (C_DEMO_RD, C_DEMO_WR, C_META_RD,
                                     C_META_WR, CTR_DTYPE, Pool, bump)


class SegmentStats(NamedTuple):
    """Per-expander migration facts, computed in-jit each segment (one
    leading expander axis under the fabric's vmap). The singles/groups
    split is exposed so the PLANNER's donor rule can use the same safe
    allocation margin the APPLY enforces (7 singles + 1 group) — a plan
    whose every move the apply would skip is a livelock, not a plan."""
    free_units: jnp.ndarray   # int32[]  cfree + 8*gfree, in chunk units
    free_singles: jnp.ndarray  # int32[] cfree.top
    free_groups: jnp.ndarray  # int32[]  gfree.top
    eligible: jnp.ndarray     # bool[P]  valid & ~promoted & chunk-backed
    referenced: jnp.ndarray   # bool[P]  metadata-cache-resident (§4.4)


def segment_stats(pool: Pool, cfg: PoolConfig) -> SegmentStats:
    """One expander's migration-planning facts. Referenced bits at page
    granularity for *compressed* pages are metadata-cache residency — the
    same recency signal the demotion engine probes to protect hot pages
    (the activity-region referenced bits cover only promoted pages, which
    never migrate)."""
    w0s = pool.meta[:, 0]
    eligible = (md.get_valid(w0s) == 1) & (md.get_promoted(w0s) == 0) & \
        (md.get_num_chunks(w0s) > 0)
    free_units = pool.cfree.top + 8 * pool.gfree.top
    ids = jnp.arange(cfg.n_pages, dtype=jnp.int32)
    sets = mcc._set_index(ids, pool.cache.tags.shape[0])
    referenced = jnp.any(pool.cache.tags[sets] == ids[:, None], axis=1)
    return SegmentStats(free_units=free_units, free_singles=pool.cfree.top,
                        free_groups=pool.gfree.top, eligible=eligible,
                        referenced=referenced)


def page_eligible(entry) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(eligible, nchunks) from a metadata entry: valid, non-promoted,
    chunk-backed — the per-move re-check every apply path shares."""
    w0 = entry[0]
    nchunks = md.get_num_chunks(w0).astype(jnp.int32)
    eligible = (md.get_valid(w0) == 1) & (md.get_promoted(w0) == 0) & \
        (nchunks > 0)
    return eligible, nchunks


def migrate_src(s: Pool, cfg: PoolConfig, policy: Policy, ospn, entry,
                nchunks) -> Pool:
    """Source half of one page move (the payload gather happens at the
    caller — the collective apply routes it over the mesh between the
    halves): charge the demotion-read + metadata traffic, free the
    chunks, invalidate the entry."""
    moved_units = (nchunks * (cfg.chunk_bytes // 64)).astype(CTR_DTYPE)
    sc = policy.charge_migration(s.counters, C_DEMO_RD, moved_units)
    sc = bump(sc, C_META_RD, ops.meta_width(cfg, ospn))
    s = ops.free_chunks(s._replace(counters=sc), cfg, entry)
    return s._replace(meta=s.meta.at[ospn].set(md.empty_entry()),
                      counters=bump(s.counters, C_META_WR,
                                    ops.meta_width(cfg, ospn)))


def migrate_dst(d: Pool, cfg: PoolConfig, policy: Policy, ospn, entry,
                nchunks, buf) -> Pool:
    """Destination half: allocate, store the routed payload, write the
    travelled metadata word with the pointers rewritten for the
    destination's allocation."""
    moved_units = (nchunks * (cfg.chunk_bytes // 64)).astype(CTR_DTYPE)
    t, ptrs, is_group = ops._alloc(d, ops.begin(d, cfg, "meta"), nchunks)
    t = ops._scatter_page_buf(t, cfg, buf, ptrs, nchunks, is_group)
    new_entry = entry
    for i in range(7):
        new_entry = md.set_ptr(new_entry, i, jnp.maximum(ptrs[i], 0))
    dc = policy.charge_migration(d.counters, C_DEMO_WR, moved_units)
    dc = bump(dc, C_META_WR, ops.meta_width(cfg, ospn))
    dc = policy.on_compress_store(dc)
    return ops.commit(d, ops._set(t._replace(counters=dc), "meta", ospn,
                                  new_entry))


def migrate_page(src: Pool, dst: Pool, cfg: PoolConfig, policy: Policy,
                 ospn) -> Tuple[Pool, Pool, jnp.ndarray]:
    """Move one page's compressed copy from ``src`` to ``dst``.

    Eligible pages are valid, non-promoted, and chunk-backed; anything else
    is a no-op (returns moved=False). The metadata word travels unchanged
    (rates, sizes, num_chunks, wr_cntr); only the chunk pointers are
    rewritten for the destination's allocation. Composed from the same
    ``migrate_src`` / ``migrate_dst`` halves the sharded collective apply
    uses, so the two paths stay bit-identical per move."""
    entry = src.meta[ospn]
    eligible, nchunks = page_eligible(entry)

    def do(carry):
        s, d = carry
        # source: read the compressed payload (nchunks * 512B), free the
        # chunks, invalidate the entry
        buf = ops._gather_page_buf(s, cfg, entry)
        s = migrate_src(s, cfg, policy, ospn, entry, nchunks)
        # destination: allocate, store, write the travelled metadata word
        d = migrate_dst(d, cfg, policy, ospn, entry, nchunks, buf)
        return s, d

    src, dst = jax.lax.cond(eligible, do, lambda c: c, (src, dst))
    return src, dst, eligible


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def spill_pages(src: Pool, dst: Pool, cfg: PoolConfig, policy: Policy,
                k: int) -> Tuple[Pool, Pool, jnp.ndarray]:
    """Migrate up to ``k`` eligible pages from ``src`` to ``dst``.

    Candidates are taken in OSPN order (deterministic; the clock engine
    already provides recency-aware victimization for *demotion* — spill
    relieves capacity, it does not rank hotness). A migration is skipped
    when the donor lacks a safe allocation margin (7 singles + 1 group),
    so spill can never corrupt the donor's freelists. Returns the updated
    pools plus int32[k] migrated OSPNs, -1-padded — the host pins those
    pages to the destination in the placement override table."""
    w0s = src.meta[:, 0]
    cand = (md.get_valid(w0s) == 1) & (md.get_promoted(w0s) == 0) & \
        (md.get_num_chunks(w0s) > 0)
    # stable order: candidate OSPNs first, in page order
    order = jnp.argsort(~cand).astype(jnp.int32)

    def body(i, carry):
        s, d, moved = carry
        ospn = order[i]
        headroom = (d.cfree.top >= 7) & (d.gfree.top >= 1)
        ok = cand[ospn] & headroom

        def do(c):
            s2, d2, m2 = c
            s2, d2, did = migrate_page(s2, d2, cfg, policy, ospn)
            m2 = m2.at[i].set(jnp.where(did, ospn, -1))
            return s2, d2, m2

        return jax.lax.cond(ok, do, lambda c: c, (s, d, moved))

    moved0 = jnp.full((k,), -1, jnp.int32)
    return jax.lax.fori_loop(0, k, body, (src, dst, moved0))


@functools.partial(jax.jit, static_argnums=(1, 2))
def apply_migrations(pools: Pool, cfg: PoolConfig, policy: Policy,
                     pages, srcs, dsts) -> Tuple[Pool, jnp.ndarray]:
    """Apply one migration epoch on the STACKED pool state in one jit call.

    ``pages``/``srcs``/``dsts`` are int32[k] (pages -1-padded): explicit
    moves a ``MigrationPolicy`` planned host-side, possibly one segment
    ago. Each move re-checks donor headroom (7 singles + 1 group, the
    safe allocation margin) against the donor's LIVE freelists and page
    eligibility against the LIVE metadata (inside ``migrate_page``), so a
    stale plan skips — never corrupts — a page whose state changed while
    the plan was in flight. Returns the updated stack plus int32[k] of
    the OSPNs that actually moved (-1 where skipped); the host turns that
    into ONE batched override-table scatter (`Placement.apply_epoch`)."""
    def body(i, carry):
        stack, moved = carry
        p, s, d = pages[i], srcs[i], dsts[i]

        def do(c):
            stack, moved = c
            src = jax.tree_util.tree_map(lambda a: a[s], stack)
            dst = jax.tree_util.tree_map(lambda a: a[d], stack)
            headroom = (dst.cfree.top >= 7) & (dst.gfree.top >= 1)

            def go(c2):
                stack2, m2 = c2
                src2, dst2, did = migrate_page(src, dst, cfg, policy, p)
                stack2 = jax.tree_util.tree_map(
                    lambda a, x: a.at[s].set(x), stack2, src2)
                stack2 = jax.tree_util.tree_map(
                    lambda a, x: a.at[d].set(x), stack2, dst2)
                return stack2, m2.at[i].set(jnp.where(did, p, -1))

            return jax.lax.cond(headroom, go, lambda c2: c2, (stack, moved))

        return jax.lax.cond((p >= 0) & (s != d), do, lambda c: c, carry)

    moved0 = jnp.full(pages.shape, -1, jnp.int32)
    return jax.lax.fori_loop(0, pages.shape[0], body, (pools, moved0))
