"""Mechanism ops (DESIGN.md §4): pure, individually-jittable state-machine
transitions over the pool regions — allocation/free, metadata
read-modify-write, store I/O, promotion (§4.1, §4.5, §4.6), demotion
(§4.4 + §4.5), and traffic accounting in 64B units.

Every function takes ``(pool, cfg, policy, ...)``: ``cfg`` fixes the
mechanism shape (region sizes, co-location, compaction, shadowing), while
``policy`` decides victim selection and charges scheme-specific traffic at
the site where it occurs (see engine/policy.py). Host-facing front-ends
(``host_read_block`` etc.) are the serial one-access path; the batched
front-end in engine/batch.py reuses ``access`` and the demotion loops.

**Write-set transitions.** A transition never passes a pool-sized leaf
(``meta``, ``activity``, the free lists' items, ``rates_table``,
``c_store``, ``p_store``) through a ``lax.cond``/``switch``/``select``: XLA
copies such a leaf whole wherever a conditional takes or returns it, and
on a v5e those copies were most of a slow access's time. A transition is
built as a ``Txn`` instead: the small state it changes (counters, the
free-list heads) plus a fixed-size write list (one metadata row, one
activity word, the pushes of each free list, the payload rows). Its
branches are conditionals over the ``Txn`` alone and read the pool by
closure; ``commit`` then applies the list unconditionally, one predicated
row or element write per slot, so the pool updates in place. Every
``on``/``ok`` argument masks a transition the same way: an access that is
off commits a write list whose slots all write back what they read.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.types import PoolConfig
from repro.core import activity as act
from repro.core import compressor as comp
from repro.core import freelist as fl
from repro.core import mcache as mcc
from repro.core import metadata as md
from repro.core.bitpack import RATE_RAW, RATE_ZERO
from repro.core.engine.policy import Policy
from repro.core.engine.state import (C_ACT_RD, C_ACT_WR, C_DATA_RD, C_DATA_WR,
                                     C_DEMO_CLEAN, C_DEMO_DIRTY, C_DEMO_RD,
                                     C_DEMO_WR, C_HOST_RD, C_HOST_WR,
                                     C_MC_HIT, C_MC_MISS, C_META_RD,
                                     C_META_WR, C_PROMO_RD, C_PROMO_WR,
                                     C_PROMOTIONS, C_RANDOM_FB,
                                     C_RECOMP_RETRY, C_ZERO_SERVED, CTR_DTYPE,
                                     Pool, bump)


def content_rates(pool: Pool, cfg: PoolConfig, ospn) -> jnp.ndarray:
    """Per-block rates from the content model (simx, payload-less mode)."""
    r = pool.rates_table[ospn]
    if not cfg.zero_elision:
        r = jnp.maximum(r, 1)
    if cfg.coloc:
        return r
    # 4KB-block mode: one rate for the whole page (zero only if all-zero)
    return jnp.max(r, keepdims=True)[:1]


def rates_to_chunks(rates: jnp.ndarray, cfg: PoolConfig):
    """(quanta_total, num_chunks) for a page with these block rates."""
    nblocks = rates.shape[0]
    vals = cfg.vals_per_page // nblocks
    qt = comp.block_quanta_table(vals)
    quanta = jnp.sum(qt[rates])
    qpc = cfg.chunk_bytes // comp.QUANTUM
    return quanta, (-(-quanta // qpc)).astype(jnp.uint32)


def meta_width(cfg: PoolConfig, ospn) -> jnp.ndarray:
    """64B accesses per metadata fetch: 1 compacted; uncompacted 283b entries
    straddle the 64B boundary for ~half of all pages (§4.7)."""
    if cfg.compact:
        return jnp.asarray(1, CTR_DTYPE)
    return (1 + (jnp.asarray(ospn, CTR_DTYPE) & 1))


# ---------------------------------------------------------------------------
# Transactions: small state + a fixed-size write list (module docstring).
# ---------------------------------------------------------------------------

class Row(NamedTuple):
    """Pending write of ``val`` at ``at`` where ``ok`` (leading axes: one
    slot per row)."""
    at: jnp.ndarray
    val: jnp.ndarray
    ok: jnp.ndarray


class Run(NamedTuple):
    """Pending pushes on one free list: ``vals[:n]`` from slot ``at``."""
    at: jnp.ndarray
    vals: jnp.ndarray
    n: jnp.ndarray


class Txn(NamedTuple):
    counters: jnp.ndarray
    ctop: jnp.ndarray
    gtop: jnp.ndarray
    ptop: jnp.ndarray
    meta: Optional[Row]       # one metadata row
    activity: Optional[Row]   # one activity word
    cfree: Optional[Run]      # up to 7 single chunks
    gfree: Optional[Run]      # one group
    pfree: Optional[Run]      # one P-chunk
    c_store: Optional[Row]    # payload chunk rows (store_payload only)
    p_store: Optional[Row]    # one payload page (store_payload only)


_TOP = {"cfree": "ctop", "gfree": "gtop", "pfree": "ptop"}
_RUN = {"cfree": 7, "gfree": 1, "pfree": 1}


def _chunk_slots(cfg: PoolConfig) -> int:
    """Payload chunk rows one transition writes at most: a block updated
    in place, then a recompressed page."""
    return cfg.block_bytes // cfg.chunk_bytes + cfg.chunks_per_page


def begin(pool: Pool, cfg: PoolConfig, *slots: str) -> Txn:
    """An empty transaction on ``pool`` with write slots for ``slots`` (of
    meta, activity, cfree, gfree, pfree); the payload slots come with
    ``cfg.store_payload``. A transaction pushes a free list or pops it,
    never both: a pop reads the committed items."""
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    no = jnp.asarray(False)
    t = Txn(counters=pool.counters, ctop=pool.cfree.top, gtop=pool.gfree.top,
            ptop=pool.pfree.top, meta=None, activity=None, cfree=None,
            gfree=None, pfree=None, c_store=None, p_store=None)
    if "meta" in slots:
        t = t._replace(meta=Row(i32(0), jnp.zeros_like(pool.meta[0]), no))
    if "activity" in slots:
        t = t._replace(activity=Row(i32(0), jnp.uint32(0), no))
    for name in ("cfree", "gfree", "pfree"):
        if name in slots:
            t = t._replace(**{name: Run(getattr(pool, name).top,
                                        jnp.full((_RUN[name],), -1, jnp.int32),
                                        i32(0))})
    if cfg.store_payload:
        r = _chunk_slots(cfg)
        t = t._replace(
            c_store=Row(jnp.zeros((r,), jnp.int32),
                        jnp.zeros((r, cfg.chunk_bytes), jnp.uint8),
                        jnp.zeros((r,), bool)),
            p_store=Row(i32(0), jnp.zeros((cfg.page_bytes,), jnp.uint8), no))
    return t


def _set(t: Txn, slot: str, at, val, ok=True) -> Txn:
    r = getattr(t, slot)
    ok = jnp.asarray(ok)
    return t._replace(**{slot: Row(jnp.where(ok, at, r.at).astype(jnp.int32),
                                   jnp.where(ok, val, r.val).astype(r.val.dtype),
                                   r.ok | ok)})


def _set_chunk(t: Txn, j: int, at, val, ok=True) -> Txn:
    """Payload chunk row ``at`` set to ``val`` where ``ok``, in slot ``j``."""
    r = t.c_store
    ok = jnp.asarray(ok)
    return t._replace(c_store=Row(r.at.at[j].set(jnp.where(ok, at, r.at[j])),
                                  r.val.at[j].set(jnp.where(ok, val, r.val[j])),
                                  r.ok.at[j].set(r.ok[j] | ok)))


def _row(pool: Pool, t: Txn, ospn) -> jnp.ndarray:
    """Metadata row ``ospn`` as the transaction left it."""
    if t.meta is None:
        return pool.meta[ospn]
    mine = t.meta.ok & (t.meta.at == ospn)
    return jnp.where(mine, t.meta.val, pool.meta[ospn])


def _page(pool: Pool, t: Txn, pidx) -> jnp.ndarray:
    """Payload page ``pidx`` as the transaction left it."""
    mine = t.p_store.ok & (t.p_store.at == pidx)
    return jnp.where(mine, t.p_store.val, pool.p_store[pidx])


def _push(t: Txn, name: str, idxs) -> Txn:
    """Push the non-negative entries of ``idxs`` onto free list ``name``."""
    r = getattr(t, name)
    top = getattr(t, _TOP[name])
    vals, n = fl.pack(jnp.atleast_1d(jnp.asarray(idxs, jnp.int32)))
    k = r.vals.shape[0]
    src = jnp.arange(k, dtype=jnp.int32) - r.n
    take = (src >= 0) & (src < n)
    new = jnp.where(take, vals[jnp.clip(src, 0, vals.shape[0] - 1)], r.vals)
    run = Run(jnp.where(r.n == 0, top, r.at), new, r.n + n)
    return t._replace(**{name: run, _TOP[name]: top + n})


def _pop_n(pool: Pool, t: Txn, name: str, k: int, n, ok=True):
    """Pop up to ``k`` of ``n`` items of free list ``name`` where ``ok``."""
    src = fl.FreeList(getattr(pool, name).items, getattr(t, _TOP[name]))
    out_fl, out = fl.pop_n(src, k, jnp.where(ok, n, 0))
    return t._replace(**{_TOP[name]: out_fl.top}), out


def _put_row(arr: jnp.ndarray, at, val, ok) -> jnp.ndarray:
    """One predicated row write: ``arr[at] = val`` where ``ok``."""
    at = jnp.clip(at, 0, arr.shape[0] - 1)
    zeros = (0,) * (arr.ndim - 1)
    old = jax.lax.dynamic_slice(arr, (at,) + zeros, (1,) + arr.shape[1:])
    new = jnp.where(ok, val.reshape(old.shape).astype(arr.dtype), old)
    return jax.lax.dynamic_update_slice(arr, new, (at,) + zeros)


def commit(pool: Pool, t: Txn) -> Pool:
    """Apply the transaction: its small state, then each write slot."""
    def run(name):
        items = getattr(pool, name).items
        r = getattr(t, name)
        if r is not None:
            items = fl.write_run(items, r.at, r.vals, r.n)
        return fl.FreeList(items, getattr(t, _TOP[name]))

    pool = pool._replace(counters=t.counters, cfree=run("cfree"),
                         gfree=run("gfree"), pfree=run("pfree"))
    if t.meta is not None:
        pool = pool._replace(meta=_put_row(pool.meta, *t.meta))
    if t.activity is not None:
        pool = pool._replace(activity=act.put_word(pool.activity, *t.activity))
    if t.c_store is not None:
        c_store = pool.c_store
        for j in range(t.c_store.ok.shape[0]):
            c_store = _put_row(c_store, t.c_store.at[j], t.c_store.val[j],
                               t.c_store.ok[j])
        pool = pool._replace(c_store=c_store,
                             p_store=_put_row(pool.p_store, *t.p_store))
    return pool


# ---------------------------------------------------------------------------
# Metadata-cache step with lazy reference update (§4.4).
# ---------------------------------------------------------------------------

def mcache_step(pool: Pool, cfg: PoolConfig, policy: Policy, ospn, on=True
                ) -> Tuple[Pool, jnp.ndarray]:
    cache, hit, evicted = mcc.access(pool.cache, ospn)
    miss_counters = bump(bump(pool.counters, C_MC_MISS),
                         C_META_RD, meta_width(cfg, ospn))
    miss_counters = policy.on_mcache_miss(miss_counters)
    counters = jnp.where(hit, bump(pool.counters, C_MC_HIT), miss_counters)
    # lazy update: evicted page, if promoted, gets its referenced bit set
    # now; the word is written only when the referenced bit flips, and an
    # already-referenced entry costs nothing (same charge as the batched
    # front-end's masked scatter in engine/batch.py)
    def touch(c):
        ev_entry = pool.meta[jnp.maximum(evicted, 0)]
        promoted = (md.get_promoted(ev_entry[0]) == 1) & \
            (md.get_valid(ev_entry[0]) == 1)
        pidx = md.get_ptr(ev_entry, md.PCHUNK_SLOT).astype(jnp.int32)
        word = pool.activity[jnp.clip(pidx, 0, pool.activity.shape[0] - 1)]
        flips = promoted & (md.act_referenced(word) == 0)
        c = jnp.where(flips, policy.charge_activity(c, C_ACT_WR), c)
        return c, jnp.where(flips, pidx, -1), md.act_set_referenced(word, 1)

    def skip(c):
        return c, jnp.int32(-1), jnp.uint32(0)

    counters, pidx, word = jax.lax.cond(evicted >= 0, touch, skip, counters)
    cache = jax.tree_util.tree_map(lambda a, b: jnp.where(on, a, b), cache,
                                   pool.cache)
    activity = act.put_word(pool.activity, pidx, word, on & (pidx >= 0))
    return pool._replace(cache=cache, activity=activity,
                         counters=jnp.where(on, counters, pool.counters)), hit


def host_count(counters: jnp.ndarray, policy: Policy, is_write, on=True
               ) -> jnp.ndarray:
    """The host-access counters of one access (``is_write`` may be traced)."""
    wr = policy.on_host_access(bump(counters, C_HOST_WR), True)
    rd = policy.on_host_access(bump(counters, C_HOST_RD), False)
    return jnp.where(on, jnp.where(is_write, wr, rd), counters)


# ---------------------------------------------------------------------------
# Payload helpers (no-ops when store_payload=False).
# ---------------------------------------------------------------------------

def _chunk_ptrs(entry: jnp.ndarray) -> jnp.ndarray:
    """int32[7] pointer slots 0..6 (slot 6 doubles as the P-chunk slot)."""
    return jnp.stack([md.get_ptr(entry, i) for i in range(7)]).astype(jnp.int32)


def _gather_page_buf(pool: Pool, cfg: PoolConfig, entry: jnp.ndarray,
                     t: Optional[Txn] = None) -> jnp.ndarray:
    """Reassemble the compacted compressed-page buffer from its chunks (as
    transaction ``t`` left them, if given)."""
    if not cfg.store_payload:
        return jnp.zeros((cfg.page_bytes,), jnp.uint8)
    w0 = entry[0]
    nchunks = md.get_num_chunks(w0).astype(jnp.int32)
    is_group = nchunks == 8                      # incompressible: aligned group
    ptrs = _chunk_ptrs(entry)
    base = ptrs[0]
    cpp = cfg.chunks_per_page
    idxs = []
    for i in range(cpp):
        single = ptrs[min(i, 6)]
        grp = base + i
        idx = jnp.where(is_group, grp, jnp.where(i < nchunks, single, 0))
        idxs.append(jnp.clip(idx, 0, pool.c_store.shape[0] - 1))
    idxs = jnp.stack(idxs)
    chunks = pool.c_store[idxs]                  # [cpp, chunk_bytes]
    if t is not None:
        pend = t.c_store
        for j in range(pend.ok.shape[0]):        # later slots win
            mine = pend.ok[j] & (idxs == pend.at[j])
            chunks = jnp.where(mine[:, None], pend.val[j][None, :], chunks)
    return chunks.reshape(cfg.page_bytes)


def _scatter_page_buf(t: Txn, cfg: PoolConfig, buf: jnp.ndarray,
                      ptrs: jnp.ndarray, nchunks, is_group, ok=True,
                      slot0: int = 0) -> Txn:
    """Queue the chunk rows of a compressed page at slots ``slot0..``."""
    if not cfg.store_payload:
        return t
    cpp = cfg.chunks_per_page
    pieces = buf.reshape(cpp, cfg.chunk_bytes)
    base = ptrs[0]
    for i in range(cpp):
        idx = jnp.where(is_group, base + i, ptrs[min(i, 6)])
        t = _set_chunk(t, slot0 + i, jnp.clip(idx, 0, cfg.n_cchunks - 1),
                       pieces[i], (is_group | (i < nchunks)) & ok)
    return t


def _clip_p(pool: Pool, pidx) -> jnp.ndarray:
    return jnp.clip(pidx, 0, max(pool.p_store.shape[0] - 1, 0))


def _read_pchunk_block(pool: Pool, cfg: PoolConfig, pidx, block_idx) -> jnp.ndarray:
    if not cfg.store_payload:
        return jnp.zeros((cfg.vals_per_block,), jnp.bfloat16)
    page = pool.p_store[_clip_p(pool, pidx)]
    b = jax.lax.dynamic_slice(page, (block_idx * cfg.block_bytes,),
                              (cfg.block_bytes,))
    from repro.core.bitpack import bytes_to_raw
    return bytes_to_raw(b)


def _page_to_bytes(vals: jnp.ndarray) -> jnp.ndarray:
    from repro.core.bitpack import raw_to_bytes
    return raw_to_bytes(vals)


def _block_mask(cfg: PoolConfig, block_idx, full: jnp.ndarray) -> jnp.ndarray:
    pos = jnp.arange(cfg.page_bytes, dtype=jnp.int32) // cfg.block_bytes
    return full | (pos == jnp.asarray(block_idx, jnp.int32))


# ---------------------------------------------------------------------------
# Chunk (de)allocation: pops move a head register, pushes queue item writes.
# ---------------------------------------------------------------------------

def _alloc(pool: Pool, t: Txn, num_chunks, ok=True
           ) -> Tuple[Txn, jnp.ndarray, jnp.ndarray]:
    """Allocate ``num_chunks`` C-chunks (8 -> one aligned group) where
    ``ok``. Returns (txn, ptrs int32[7], is_group)."""
    is_group = num_chunks >= 8
    t, base = _pop_n(pool, t, "gfree", 1, 1, ok & is_group)
    ptrs_g = jnp.full((7,), -1, jnp.int32).at[0].set(base[0])
    t, ptrs_s = _pop_n(pool, t, "cfree", 7, jnp.minimum(num_chunks, 7),
                       ok & ~is_group)
    return t, jnp.where(is_group, ptrs_g, ptrs_s), is_group


def _free(t: Txn, entry: jnp.ndarray, ok=True) -> Txn:
    """Release all C-chunks referenced by ``entry`` where ``ok`` (no-op if
    none)."""
    nchunks = md.get_num_chunks(entry[0]).astype(jnp.int32)
    is_group = nchunks == 8
    ptrs = _chunk_ptrs(entry)
    ok = ok & (nchunks > 0)
    t = _push(t, "gfree", jnp.where(ok & is_group, ptrs[0], -1))
    singles = jnp.where(jnp.arange(7) < nchunks, ptrs, -1)
    return _push(t, "cfree", jnp.where(ok & ~is_group, singles, -1))


def free_chunks(pool: Pool, cfg: PoolConfig, entry: jnp.ndarray) -> Pool:
    """Release all C-chunks referenced by ``entry`` (no-op if none)."""
    return commit(pool, _free(begin(pool, cfg, "cfree", "gfree"), entry))


# ---------------------------------------------------------------------------
# Demotion (§4.4 + §4.5).
# ---------------------------------------------------------------------------

def _header_4kb(rate, nchunks) -> jnp.ndarray:
    """word0 for co-location-disabled mode: rate kept in block_sz[0]."""
    w = jnp.uint32(0)
    w = md.set_block_type(w, 0, jnp.where(rate == RATE_ZERO, md.BT_ZERO,
                          jnp.where(rate == RATE_RAW, md.BT_INCOMP, md.BT_COMP)))
    w = md.set_block_sz(w, 0, rate)
    w = md.set_valid(w, 1)
    return w


def _compressed_entry(cfg: PoolConfig, rates, nchunks, ptrs) -> jnp.ndarray:
    """The entry of a freshly compressed page stored behind ``ptrs``."""
    w = md.header_from_rates(rates) if cfg.coloc else \
        _header_4kb(rates[0], nchunks)
    w = md.set_num_chunks(w, nchunks)
    entry = md.empty_entry().at[0].set(w)
    for i in range(7):
        entry = md.set_ptr(entry, i, jnp.maximum(ptrs[i], 0))
    return entry


def _select(pool: Pool, policy: Policy, force=False) -> Tuple[Pool, jnp.ndarray]:
    """Run the victim-selection policy once: the scan's traffic, hand, rng
    and second-chance clears land in ``pool``. Returns the victim's OSPN
    (-1 if none)."""
    rng, sub = jax.random.split(pool.rng)
    v = policy.select_victim(pool.activity, pool.hand, pool.cache, sub,
                             force=force)
    counters = policy.charge_activity(pool.counters, C_ACT_RD,
                                      v.groups_scanned.astype(CTR_DTYPE))
    counters = policy.charge_activity(counters, C_ACT_WR,
                                      v.groups_scanned.astype(CTR_DTYPE))
    counters = jnp.where(v.used_random, bump(counters, C_RANDOM_FB), counters)
    activity = act.clear_scanned(pool.activity, pool.hand, v.groups_scanned)
    return pool._replace(activity=activity, hand=v.hand, rng=rng,
                         counters=counters), v.victim_ospn


def _release(pool: Pool, t: Txn, victim) -> Txn:
    """Free the victim's P-chunk and activity entry (no-op if none)."""
    have = victim >= 0
    entry = pool.meta[jnp.maximum(victim, 0)]
    pidx = md.get_ptr(entry, md.PCHUNK_SLOT).astype(jnp.int32)
    t = _push(t, "pfree", jnp.where(have, pidx, -1))
    return _set(t, "activity", pidx, jnp.uint32(0), have)


def _store_victim(pool: Pool, cfg: PoolConfig, policy: Policy, t: Txn,
                  victim, entry, dirty_page=None) -> Txn:
    """Store demotion victim ``victim`` (no-op if -1) whose entry is
    ``entry``: a clean page re-validates its shadow by flipping type fields
    only (§4.5); a dirty one is recompressed into fresh chunks (§4.2 cost).
    ``dirty_page`` is the recompressed (buf, rates, nchunks) when the
    caller batched it; else it is computed here."""
    have = victim >= 0
    ospn = jnp.maximum(victim, 0)
    w0 = entry[0]
    clean = (md.get_dirty(w0) == 0) & (md.get_shadow_valid(w0) == 1)
    dirty = have & ~clean

    # clean: the shadow chunks become the page again
    nblocks = cfg.blocks_per_page if cfg.coloc else 1
    raw_sz = 7 if cfg.coloc else RATE_RAW  # non-coloc sz holds the rate
    w = w0
    for i in range(nblocks):
        bt = md.get_block_type(w, i)
        sz = md.get_block_sz(w, i)
        restored = jnp.where(sz == raw_sz, md.BT_INCOMP, md.BT_COMP)
        w = md.set_block_type(w, i, jnp.where(bt == md.BT_PROM, restored, bt))
    w = md.set_promoted(w, 0)
    w = md.set_shadow_valid(w, 0)
    clean_entry = entry.at[0].set(w)
    cc = bump(t.counters, C_META_WR, meta_width(cfg, ospn))
    cc = bump(cc, C_DEMO_CLEAN)
    cc = policy.on_demotion(cc, clean=True)

    # dirty: read the promoted page, recompress, store chunks
    if dirty_page is not None:
        buf, rates, nchunks = dirty_page
    elif cfg.store_payload:
        from repro.core.bitpack import bytes_to_raw
        pidx = md.get_ptr(entry, md.PCHUNK_SLOT).astype(jnp.int32)

        def encode(_):
            b, r, _, n = comp.encode_page(
                bytes_to_raw(pool.p_store[_clip_p(pool, pidx)]), cfg)
            return b, r, n

        def skip(_):
            return jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                                          jax.eval_shape(encode, None))

        buf, rates, nchunks = jax.lax.cond(dirty, encode, skip, None)
    else:
        # metadata-only mode: compressed sizes come from the content model
        # instead of actual bytes (simx)
        buf = jnp.zeros((cfg.page_bytes,), jnp.uint8)
        rates = content_rates(pool, cfg, ospn)
        _, nchunks = rates_to_chunks(rates, cfg)
    t, ptrs, is_group = _alloc(pool, t, nchunks, dirty)
    t = _scatter_page_buf(t, cfg, buf, ptrs, nchunks, is_group, dirty)
    dirty_entry = _compressed_entry(cfg, rates, nchunks, ptrs)
    cd = policy.charge_migration(t.counters, C_DEMO_RD, cfg.page_bytes // 64)
    cd = policy.charge_migration(
        cd, C_DEMO_WR, (nchunks * (cfg.chunk_bytes // 64)).astype(CTR_DTYPE))
    cd = bump(cd, C_META_WR, meta_width(cfg, ospn))
    cd = bump(cd, C_DEMO_DIRTY)
    cd = policy.on_compress_store(cd)
    cd = policy.on_demotion(cd, clean=False)

    t = _set(t, "meta", ospn, jnp.where(clean, clean_entry, dirty_entry), have)
    return t._replace(counters=jnp.where(
        have, jnp.where(clean, cc, cd), t.counters))


def demote_one(pool: Pool, cfg: PoolConfig, policy: Policy, force=False) -> Pool:
    """Run the victim-selection policy once and demote the selected victim."""
    pool, victim = _select(pool, policy, force)

    def demote(t: Txn) -> Txn:
        entry = pool.meta[jnp.maximum(victim, 0)]
        t = _release(pool, t, victim)
        return _store_victim(pool, cfg, policy, t, victim, entry)

    t = begin(pool, cfg, "meta", "activity", "pfree")
    return commit(pool, jax.lax.cond(victim >= 0, demote, lambda q: q, t))


def _use_batched_demote(cfg: PoolConfig) -> bool:
    mode = getattr(cfg, "fused_demote", "auto")
    if mode == "auto":
        return comp.resolve_impl(cfg) == "kernel"
    return mode == "on"


def demote_batch(pool: Pool, cfg: PoolConfig, policy: Policy,
                 max_demotes: int, target, on=True) -> Pool:
    """Demote up to ``max_demotes`` victims with ONE batched recompression
    (a single fused-kernel launch on TPU) instead of a serial chain of
    per-victim ``encode_page`` calls.

    Bit-identical to the serial loop (tests/test_qpack_fused.py): phase 1
    replays victim selection serially (activity/hand/rng/pfree evolve in the
    exact serial order — demote bodies never touch them), phase 2 recompresses
    all dirty victims in one ``encode_pages`` call (victims are distinct, so
    per-victim meta/p_store reads see the same values the serial loop reads),
    and phase 3 applies the metadata/chunk effects in victim order (cfree/
    gfree pops in the serial sequence; counters are commutative adds)."""
    # -- phase 1: victim selection + P-chunk release, serial semantics -------
    def sel_cond(carry):
        i, p, _ = carry
        return on & (i < max_demotes) & (fl.free_count(p.pfree) < target)

    def sel_body(carry):
        i, p, victims = carry
        p, victim = _select(p, policy)
        t = jax.lax.cond(victim >= 0, lambda q: _release(p, q, victim),
                         lambda q: q, begin(p, cfg, "activity", "pfree"))
        p = commit(p, t)
        return i + 1, p, victims.at[i].set(victim)

    victims0 = jnp.full((max_demotes,), -1, jnp.int32)
    n_sel, pool, victims = jax.lax.while_loop(
        sel_cond, sel_body, (jnp.asarray(0, jnp.int32), pool, victims0))

    # -- phase 2: batched recompression of every dirty victim ----------------
    ospns = jnp.maximum(victims, 0)
    entries = pool.meta[ospns]                       # [K, ENTRY_WORDS]
    if cfg.store_payload:
        from repro.core.bitpack import bytes_to_raw
        pidxs = jax.vmap(lambda e: md.get_ptr(e, md.PCHUNK_SLOT))(
            entries).astype(jnp.int32)
        vals = jax.vmap(bytes_to_raw)(pool.p_store[_clip_p(pool, pidxs)])
        bufs, rates, _, nchunks = comp.encode_pages(vals, cfg)
    else:
        bufs = jnp.zeros((max_demotes, cfg.page_bytes), jnp.uint8)
        rates = jax.vmap(lambda o: content_rates(pool, cfg, o))(ospns)
        nchunks = jax.vmap(lambda r: rates_to_chunks(r, cfg)[1])(rates)

    # -- phase 3: per-victim metadata/chunk effects, in victim order ---------
    # (over the selections phase 1 made; the slots after them hold no victim)
    def fin_body(carry):
        i, p = carry
        t = _store_victim(p, cfg, policy, begin(p, cfg, "meta"), victims[i],
                          entries[i], (bufs[i], rates[i], nchunks[i]))
        return i + 1, commit(p, t)

    return jax.lax.while_loop(lambda c: c[0] < n_sel, fin_body,
                              (jnp.asarray(0, jnp.int32), pool))[1]


def demote_if_needed(pool: Pool, cfg: PoolConfig, policy: Policy,
                     max_demotes: int = 2, watermark: int = 0, on=True
                     ) -> Pool:
    """Keep >= watermark free P-chunks (the paper's background engine, amortized
    into the request path: at most ``max_demotes`` per host op). ``watermark``
    overrides ``cfg.demote_watermark`` when > 0 — the batched front-end tops
    up to a higher target once per window instead of checking per access.

    With ``cfg.fused_demote`` resolved on (or "auto" on TPU) the victims are
    recompressed by one batched kernel launch (``demote_batch``) instead of a
    serial chain of per-victim encodes. The loop stops at the first
    iteration that finds the watermark met: a skipped iteration would
    change nothing."""
    target = watermark or cfg.demote_watermark
    if max_demotes > 1 and _use_batched_demote(cfg):
        return demote_batch(pool, cfg, policy, max_demotes, target, on)

    def cond(carry):
        i, p = carry
        return on & (i < max_demotes) & (fl.free_count(p.pfree) < target)

    def body(carry):
        i, p = carry
        return i + 1, demote_one(p, cfg, policy)

    return jax.lax.while_loop(cond, body, (jnp.asarray(0, jnp.int32), pool))[1]


def ensure_free_pchunk(pool: Pool, cfg: PoolConfig, policy: Policy,
                       tries: int = 4, on=True) -> Pool:
    """Guarantee at least one free P-chunk before a promotion pops the list.

    The last attempts *force* the clock's random fallback to consider
    cache-resident pages — an emergency valve that cannot trigger at the
    paper's region ratios but keeps small test/sim configs live-safe (a pop
    from an empty list would alias P-chunk 0 and corrupt another page)."""
    def cond(carry):
        i, p = carry
        return on & (i < tries) & (fl.free_count(p.pfree) == 0)

    def body(carry):
        i, p = carry
        return i + 1, demote_one(p, cfg, policy, force=(i >= tries // 2))

    return jax.lax.while_loop(cond, body, (jnp.asarray(0, jnp.int32), pool))[1]


# ---------------------------------------------------------------------------
# Promotion (§4.1, §4.5, §4.6).
# ---------------------------------------------------------------------------

def _rates_of(entry: jnp.ndarray, cfg: PoolConfig) -> jnp.ndarray:
    if cfg.coloc:
        return md.rates_from_header(entry[0], cfg.blocks_per_page)
    return md.get_block_sz(entry[0], 0).astype(jnp.int32)[None]


def _promote(pool: Pool, cfg: PoolConfig, policy: Policy, t: Txn, ospn,
             block_idx) -> Txn:
    """Promote page ``ospn`` (fine-grained: materialize only ``block_idx``
    when the shadow can be kept; see DESIGN.md for the 7-chunk exception).
    The caller has guaranteed a free P-chunk (``ensure_free_pchunk``) for
    a page not yet promoted."""
    entry = _row(pool, t, ospn)
    w0 = entry[0]
    already = md.get_promoted(w0) == 1
    nchunks = md.get_num_chunks(w0).astype(jnp.int32)

    t2, pidx_new = _pop_n(pool, t, "pfree", 1, 1, ~already)
    t = t2
    pidx = jnp.where(already, md.get_ptr(entry, md.PCHUNK_SLOT).astype(jnp.int32),
                     pidx_new[0])

    # shadow feasibility: slot 6 must be free for the P-chunk pointer
    can_shadow = (nchunks <= 6) | (nchunks == 8)
    full_materialize = (~can_shadow) | (not cfg.coloc)

    rates = _rates_of(entry, cfg)
    nblocks = cfg.blocks_per_page if cfg.coloc else 1

    # traffic: chunk reads. fine-grained reads only the target block's quanta.
    q_all = comp.page_compressed_bytes(rates, cfg.vals_per_page // nblocks) // 64
    if cfg.coloc:
        qt = comp.block_quanta_table(cfg.vals_per_block)
        q_blk = (qt[rates[jnp.minimum(block_idx, nblocks - 1)]] *
                 (comp.QUANTUM // 64))
    else:
        q_blk = q_all
    rd = jnp.where(full_materialize, q_all, q_blk).astype(CTR_DTYPE)
    counters = policy.charge_migration(t.counters, C_PROMO_RD, rd)

    # materialize into the P-chunk
    if cfg.store_payload:
        buf = _gather_page_buf(pool, cfg, entry, t)
        page_bytes_arr = _page_to_bytes(comp.decode_page(buf, rates, cfg))
        safe = _clip_p(pool, pidx)
        if cfg.coloc:
            mask = _block_mask(cfg, block_idx, full_materialize)
            newpage = jnp.where(mask, page_bytes_arr, _page(pool, t, safe))
        else:
            newpage = page_bytes_arr
        t = _set(t, "p_store", safe, newpage)
    wr = jnp.where(full_materialize, cfg.page_bytes // 64,
                   cfg.block_bytes // 64).astype(CTR_DTYPE)
    counters = policy.charge_migration(counters, C_PROMO_WR, wr)
    counters = bump(counters, C_PROMOTIONS)

    # metadata update
    w = w0
    if cfg.coloc:
        for i in range(nblocks):
            is_tgt = (jnp.asarray(block_idx) == i) | full_materialize
            bt = md.get_block_type(w, i)
            promote_this = is_tgt & (bt != md.BT_ZERO)
            w = md.set_block_type(w, i, jnp.where(promote_this, md.BT_PROM, bt))
    else:
        w = md.set_block_type(w, 0, md.BT_PROM)
    w = md.set_promoted(w, 1)
    keep_shadow = can_shadow & jnp.asarray(cfg.shadow)
    w = md.set_shadow_valid(w, keep_shadow.astype(jnp.uint32))
    w = md.set_dirty(w, (~keep_shadow).astype(jnp.uint32))
    w = md.set_num_chunks(w, jnp.where(keep_shadow, md.get_num_chunks(w0),
                                       jnp.uint32(0)))
    new_entry = entry.at[0].set(w)
    new_entry = md.set_ptr(new_entry, md.PCHUNK_SLOT, jnp.maximum(pidx, 0))

    # if the shadow cannot be kept (or shadowing disabled), free the chunks now
    t = _free(t, entry, ~keep_shadow)
    counters = bump(counters, C_META_WR, meta_width(cfg, ospn))
    t = _set(t._replace(counters=counters), "meta", ospn, new_entry)
    # activity entry (arrives referenced=1)
    return _set(t, "activity", pidx, md.act_pack(1, 1, ospn), ~already)


# ---------------------------------------------------------------------------
# Host-facing access bodies (block granularity; 64B accounting is analytic).
# The bodies assume the per-access prologue (background demotion + metadata
# cache step + host counter) already ran — both the serial front-ends below
# and the batched front-end (engine/batch.py) provide it.
# ---------------------------------------------------------------------------

def _write_page(pool: Pool, cfg: PoolConfig, t: Txn, ospn,
                vals: jnp.ndarray) -> Txn:
    """First-touch page write: lands uncompressed in the promoted region
    (promotion-based management stores first-touched data hot, §4). The
    caller has guaranteed a free P-chunk for a page not yet promoted."""
    entry = _row(pool, t, ospn)
    # free any previous incarnation
    t = _free(t, entry)
    was_promoted = md.get_promoted(entry[0]) == 1
    old_pidx = md.get_ptr(entry, md.PCHUNK_SLOT).astype(jnp.int32)
    t, pidx_new = _pop_n(pool, t, "pfree", 1, 1, ~was_promoted)
    pidx = jnp.where(was_promoted, old_pidx, pidx_new[0])
    if cfg.store_payload:
        t = _set(t, "p_store", _clip_p(pool, pidx), _page_to_bytes(vals))
    nblocks = cfg.blocks_per_page if cfg.coloc else 1
    w = jnp.uint32(0)
    for i in range(nblocks):
        w = md.set_block_type(w, i, md.BT_PROM)
        w = md.set_block_sz(w, i, 0)
    w = md.set_valid(w, 1)
    w = md.set_promoted(w, 1)
    w = md.set_dirty(w, 1)
    new_entry = md.empty_entry().at[0].set(w)
    new_entry = md.set_ptr(new_entry, md.PCHUNK_SLOT, jnp.maximum(pidx, 0))
    counters = bump(t.counters, C_DATA_WR, cfg.page_bytes // 64)
    counters = bump(counters, C_META_WR, meta_width(cfg, ospn))
    t = _set(t._replace(counters=counters), "meta", ospn, new_entry)
    return _set(t, "activity", pidx, md.act_pack(1, 1, ospn))


def write_page_op(pool: Pool, cfg: PoolConfig, policy: Policy, ospn,
                  vals: jnp.ndarray) -> Pool:
    """First-touch page write (``_write_page``) with its P-chunk ensured."""
    was_promoted0 = md.get_promoted(pool.meta[ospn][0]) == 1
    pool = ensure_free_pchunk(pool, cfg, policy, on=~was_promoted0)
    t = begin(pool, cfg, "meta", "activity", "cfree", "gfree")
    return commit(pool, _write_page(pool, cfg, t, ospn, vals))


def _block_state(entry: jnp.ndarray, cfg: PoolConfig, block_idx):
    w0 = entry[0]
    if cfg.coloc:
        bt = md.get_block_type_dyn(w0, block_idx)
    else:
        bt = md.get_block_type(w0, 0)
    return (md.get_valid(w0) == 1, md.get_promoted(w0) == 1, bt)


def _write_inplace(pool: Pool, cfg: PoolConfig, policy: Policy, t: Txn, ospn,
                   block_idx, vals: jnp.ndarray) -> Txn:
    """§4.1.2: incompressible (raw, non-promoted) pages are updated in
    place; wr_cntr counts updates and triggers a recompression attempt at
    the threshold (the page may have become compressible)."""
    entry0 = _row(pool, t, ospn)
    ww = entry0[0]
    if cfg.store_payload:
        from repro.core.bitpack import raw_to_bytes
        base = md.get_ptr(entry0, 0).astype(jnp.int32)
        bb = raw_to_bytes(vals.astype(jnp.bfloat16))
        half = cfg.chunk_bytes
        cpb = cfg.block_bytes // cfg.chunk_bytes  # chunks per block (2)
        for j in range(cpb):
            idx = jnp.clip(base + block_idx * cpb + j, 0, cfg.n_cchunks - 1)
            t = _set_chunk(t, j, idx,
                           jax.lax.dynamic_slice(bb, (j * half,), (half,)))
    t = t._replace(counters=bump(t.counters, C_DATA_WR, cfg.block_bytes // 64))
    cntr = md.get_wr_cntr(ww)
    trip = (cntr + 1) >= cfg.wr_thresh

    def retry(q: Txn) -> Txn:
        # recompression attempt: read the page, re-encode
        if cfg.store_payload:
            buf0 = _gather_page_buf(pool, cfg, _row(pool, q, ospn), q)
            from repro.core.bitpack import bytes_to_raw
            buf, rates, _, nch = comp.encode_page(bytes_to_raw(buf0), cfg)
        else:
            buf = jnp.zeros((cfg.page_bytes,), jnp.uint8)
            rates = content_rates(pool, cfg, ospn)
            _, nch = rates_to_chunks(rates, cfg)
        cc = policy.charge_migration(q.counters, C_DEMO_RD,
                                     cfg.page_bytes // 64)
        cc = bump(cc, C_RECOMP_RETRY)
        # every retry is a compression-engine store attempt: zsmalloc-
        # style bookkeeping is paid whether or not the page compresses
        cc = policy.on_compress_store(cc)
        q = q._replace(counters=cc)

        def compressible(r: Txn) -> Txn:
            r = _free(r, _row(pool, r, ospn))
            r, ptrs, is_group = _alloc(pool, r, nch)
            r = _scatter_page_buf(r, cfg, buf, ptrs, nch, is_group,
                                  slot0=cfg.block_bytes // cfg.chunk_bytes)
            ccc = policy.charge_migration(
                r.counters, C_DEMO_WR,
                (nch * (cfg.chunk_bytes // 64)).astype(CTR_DTYPE))
            ccc = bump(ccc, C_META_WR, meta_width(cfg, ospn))
            return _set(r._replace(counters=ccc), "meta", ospn,
                        _compressed_entry(cfg, rates, nch, ptrs))

        def still_raw(r: Txn) -> Txn:
            e = _row(pool, r, ospn)
            return _set(r, "meta", ospn, e.at[0].set(md.set_wr_cntr(e[0], 0)))

        return jax.lax.cond(nch < 8, compressible, still_raw, q)

    def just_count(q: Txn) -> Txn:
        e = _row(pool, q, ospn)
        w = md.set_wr_cntr(e[0], cntr + 1)
        q = q._replace(counters=bump(q.counters, C_META_WR,
                                     meta_width(cfg, ospn)))
        return _set(q, "meta", ospn, e.at[0].set(w))

    return jax.lax.cond(trip, retry, just_count, t)


def _write_update(pool: Pool, cfg: PoolConfig, policy: Policy, t: Txn, ospn,
                  block_idx, vals: jnp.ndarray) -> Txn:
    """A write to a valid page that is promoted or compressible: promote
    it if it is not (full materialization: a write invalidates the shadow
    anyway), fill its still-cold blocks, drop the shadow (the §4.5 update
    moment) and write the block."""
    promoted = md.get_promoted(_row(pool, t, ospn)[0]) == 1
    t = jax.lax.cond(promoted, lambda q: q,
                     lambda q: _promote(pool, cfg, policy, q, ospn, block_idx),
                     t)
    e = _row(pool, t, ospn)
    ww = e[0]
    # materialize any still-cold blocks before dropping the chunks
    nblocks = cfg.blocks_per_page if cfg.coloc else 1
    pidx = md.get_ptr(e, md.PCHUNK_SLOT).astype(jnp.int32)
    needs_fill = jnp.asarray(False)
    for i in range(nblocks):
        bt = md.get_block_type(ww, i)
        needs_fill = needs_fill | ((bt != md.BT_PROM) & (bt != md.BT_ZERO))

    def fill_cold(q: Txn) -> Txn:
        rates = _rates_of(e, cfg)
        if cfg.store_payload:
            buf = _gather_page_buf(pool, cfg, e, q)
            pb = _page_to_bytes(comp.decode_page(buf, rates, cfg))
            safe = _clip_p(pool, pidx)
            pos = jnp.arange(cfg.page_bytes, dtype=jnp.int32) // cfg.block_bytes
            keep_hot = jnp.zeros((cfg.page_bytes,), jnp.bool_)
            for i in range(nblocks):
                hot_i = md.get_block_type(ww, i) == md.BT_PROM
                keep_hot = keep_hot | (hot_i & (pos == i))
            q = _set(q, "p_store", safe,
                     jnp.where(keep_hot, _page(pool, q, safe), pb))
        nb = comp.page_compressed_bytes(rates, cfg.vals_per_page // rates.shape[0]) // 64
        c = policy.charge_migration(q.counters, C_PROMO_RD,
                                    nb.astype(CTR_DTYPE))
        c = policy.charge_migration(c, C_PROMO_WR, cfg.page_bytes // 64)
        return q._replace(counters=c)

    t = jax.lax.cond(needs_fill, fill_cold, lambda q: q, t)
    # drop the shadow (the update moment, §4.5)
    t = _free(t, e)
    ww2 = ww
    for i in range(nblocks):
        ww2 = md.set_block_type(ww2, i, md.BT_PROM)
    ww2 = md.set_num_chunks(ww2, 0)
    ww2 = md.set_shadow_valid(ww2, 0)
    ww2 = md.set_dirty(ww2, 1)
    new_entry = e.at[0].set(ww2)
    for i in range(6):
        new_entry = md.set_ptr(new_entry, i, 0)
    t = _set(t, "meta", ospn, new_entry)
    # the actual block write + activity touch (write = an access: hot)
    if cfg.store_payload:
        from repro.core.bitpack import raw_to_bytes
        safe = _clip_p(pool, pidx)
        page = jax.lax.dynamic_update_slice(
            _page(pool, t, safe), raw_to_bytes(vals.astype(jnp.bfloat16)),
            (block_idx * cfg.block_bytes,))
        t = _set(t, "p_store", safe, page)
    c = bump(t.counters, C_DATA_WR, cfg.block_bytes // 64)
    c = bump(c, C_META_WR, meta_width(cfg, ospn))
    return t._replace(counters=c)


# access cases (``access``'s switch)
_NONE, _PROMOTE, _FRESH, _INPLACE, _UPDATE = range(5)


def access(pool: Pool, cfg: PoolConfig, policy: Policy, ospn, block_idx,
           is_write, vals: jnp.ndarray, on=True) -> Tuple[Pool, jnp.ndarray]:
    """One block access after its prologue (paper Fig. 3 flow for a read;
    writes promote, §4.5), where ``on``. ``is_write`` may be traced.
    Returns (pool, bf16 values read; zeros for a write).

    Reads: a zero or hot block is served as it is, an invalid page reads
    zeros, anything else is promoted. Writes: an invalid page is written
    whole (``_write_page``), an incompressible resident page in place,
    anything else is updated through promotion. The P-chunk a promotion or
    first write needs is ensured first, then one transaction does the rest."""
    is_write = jnp.asarray(is_write)
    rd = on & ~is_write
    wr = on & is_write

    def classify(entry):
        valid, promoted, bt = _block_state(entry, cfg, block_idx)
        is_zero = valid & (bt == md.BT_ZERO)
        is_hot = valid & promoted & (bt == md.BT_PROM)
        needs_promo = valid & (~is_zero) & (~is_hot)
        inplace = (~promoted) & (md.get_num_chunks(entry[0]) == 8)
        case = jnp.where(
            rd & needs_promo, _PROMOTE,
            jnp.where(wr, jnp.where(~valid, _FRESH,
                                    jnp.where(inplace, _INPLACE, _UPDATE)),
                      _NONE)).astype(jnp.int32)
        return case, ~promoted, is_zero, is_hot, needs_promo

    case, unpromoted = classify(pool.meta[ospn])[:2]
    pool = ensure_free_pchunk(pool, cfg, policy, on=unpromoted
                              & (case != _NONE) & (case != _INPLACE))
    # the demotions above touch promoted pages only, so the entry reads
    # the same after them; it is read again so that no read of the
    # metadata from before the demotions outlives their writes
    case, _, is_zero, is_hot, needs_promo = classify(pool.meta[ospn])

    page = jnp.zeros((cfg.vals_per_page,), jnp.bfloat16)
    page = jax.lax.dynamic_update_slice(page, vals.astype(jnp.bfloat16),
                                        (block_idx * cfg.vals_per_block,))
    t = begin(pool, cfg, "meta", "activity", "cfree", "gfree")
    c = t.counters
    c = jnp.where(rd & is_zero, bump(c, C_ZERO_SERVED), c)
    c = jnp.where(rd & is_hot, bump(c, C_DATA_RD, cfg.block_bytes // 64), c)
    t = jax.lax.switch(case, [
        lambda q: q,
        lambda q: _promote(pool, cfg, policy, q, ospn, block_idx),
        lambda q: _write_page(pool, cfg, q, ospn, page),
        lambda q: _write_inplace(pool, cfg, policy, q, ospn, block_idx, vals),
        lambda q: _write_update(pool, cfg, policy, q, ospn, block_idx, vals),
    ], t._replace(counters=c))
    pool = commit(pool, t)
    if not cfg.store_payload:
        return pool, jnp.zeros((cfg.vals_per_block,), jnp.bfloat16)
    pidx = md.get_ptr(pool.meta[ospn], md.PCHUNK_SLOT).astype(jnp.int32)
    out = _read_pchunk_block(pool, cfg, pidx, block_idx)
    return pool, jnp.where(rd & (is_hot | needs_promo), out, 0).astype(
        jnp.bfloat16)


def read_block_op(pool: Pool, cfg: PoolConfig, policy: Policy, ospn, block_idx
                  ) -> Tuple[Pool, jnp.ndarray]:
    """Read one 1KB block (paper Fig. 3 flow). Returns (pool, bf16 values)."""
    return access(pool, cfg, policy, ospn, block_idx, False,
                  jnp.zeros((cfg.vals_per_block,), jnp.bfloat16))


def write_block_op(pool: Pool, cfg: PoolConfig, policy: Policy, ospn,
                   block_idx, vals: jnp.ndarray) -> Pool:
    """Write one 1KB block. Writes promote (whole-page materialization so the
    page's chunks can be released — §4.5: updates invalidate the shadow)."""
    return access(pool, cfg, policy, ospn, block_idx, True, vals)[0]


# ---------------------------------------------------------------------------
# Serial host-facing front-ends: per-access prologue + body, jitted.
# ---------------------------------------------------------------------------

def _prologue(pool: Pool, cfg: PoolConfig, policy: Policy, ospn, is_write,
              on=True) -> Pool:
    pool = demote_if_needed(pool, cfg, policy, on=on)
    pool, _ = mcache_step(pool, cfg, policy, ospn, on)
    return pool._replace(counters=host_count(pool.counters, policy, is_write,
                                             on))


def _host_write_page(pool: Pool, cfg: PoolConfig, policy: Policy, ospn,
                     vals: jnp.ndarray) -> Pool:
    pool = _prologue(pool, cfg, policy, ospn, is_write=True)
    return write_page_op(pool, cfg, policy, ospn, vals)


def _host_read_block(pool: Pool, cfg: PoolConfig, policy: Policy, ospn,
                     block_idx) -> Tuple[Pool, jnp.ndarray]:
    pool = _prologue(pool, cfg, policy, ospn, is_write=False)
    return read_block_op(pool, cfg, policy, ospn, block_idx)


def _host_write_block(pool: Pool, cfg: PoolConfig, policy: Policy, ospn,
                      block_idx, vals: jnp.ndarray) -> Pool:
    pool = _prologue(pool, cfg, policy, ospn, is_write=True)
    return write_block_op(pool, cfg, policy, ospn, block_idx, vals)


host_write_page = functools.partial(jax.jit, static_argnums=(1, 2))(_host_write_page)
host_read_block = functools.partial(jax.jit, static_argnums=(1, 2))(_host_read_block)
host_write_block = functools.partial(jax.jit, static_argnums=(1, 2))(_host_write_block)
