"""Plain reference of the IBEX pool replay: the same operations on the same
data, one access at a time, in numpy and Python integers.

It follows the pool's documented semantics (DESIGN.md §3-§6): 32-byte
metadata entries, the page-activity clock with lazy referenced-bit updates
on metadata-cache eviction, shadowed promotion, co-location, compaction and
zero elision, and the batched replay's window semantics (a background
demotion top-up once per window, a window-granular metadata cache, fast
accesses accounted against the window-start metadata and the rest replayed
in order). It imports nothing of the program; ``jax.random`` supplies the
clock's random fallback from the same seed the pool starts with.

Only the configuration the benchmark runs is implemented (payload-less,
``coloc``, ``compact``, ``zero_elision``, one ``ibex`` policy, window
cadence). The controls each break one guarantee the configuration states:
``break_shadow=True`` treats clean demotions as dirty (shadowed promotion);
``break_mcache=True`` keeps half the metadata cache's stated ways (Table 1's
16-way cache modelled as 8-way).
"""
from __future__ import annotations

import functools

import numpy as np

# counter slots (the program's counter vector, in order)
(META_RD, META_WR, DATA_RD, DATA_WR, PROMO_RD, PROMO_WR, DEMO_RD, DEMO_WR,
 ACT_RD, ACT_WR, ZERO_SERVED, RANDOM_FB, DEMO_CLEAN, DEMO_DIRTY, PROMOTIONS,
 HOST_RD, HOST_WR, MC_HIT, MC_MISS, RECOMP_RETRY) = range(20)
N_COUNTERS = 20

BT_ZERO, BT_COMP, BT_PROM, BT_INCOMP = 0, 1, 2, 3
RATE_TO_SZ = (0, 2, 4, 7)
RATE_TO_BT = (BT_ZERO, BT_COMP, BT_COMP, BT_INCOMP)
SZ_TO_RATE = (0, 0, 1, 0, 2, 0, 0, 3)
PTR_MASK = (1 << 29) - 1
PSLOT = 6                       # pointer slot 6 (word 7) holds the P-chunk
GROUP = 16                      # activity entries per 64B fetch
ACT_ALLOC = 1 << 31
ACT_REF = 1 << 30
ACT_OSPN = (1 << 30) - 1
U32 = 0xFFFFFFFF


def _field(w: int, lo: int, width: int) -> int:
    return (w >> lo) & ((1 << width) - 1)


def _set(w: int, lo: int, width: int, v: int) -> int:
    m = (1 << width) - 1
    return (w & ~(m << lo) & U32) | ((int(v) & m) << lo)


def bt(w, i):
    return _field(w, 5 * i, 2)


def sz(w, i):
    return _field(w, 5 * i + 2, 3)


def nchunks_of(w):
    return _field(w, 20, 4)


@functools.lru_cache(maxsize=None)
def _jax_random():
    """``split``, ``categorical`` and ``PRNGKey``, jitted, on the host CPU
    device (scalar work that a chip would only wait on)."""
    import jax
    import jax.numpy as jnp
    cpu = jax.devices("cpu")[0]
    split = jax.jit(lambda k: jax.random.split(k))
    cat = jax.jit(lambda k, w: jax.random.categorical(
        k, jnp.log(w.astype(jnp.float32) + 1e-9)))
    key = jax.jit(lambda s: jax.random.PRNGKey(s))

    def on_cpu(f):
        def g(*a):
            with jax.default_device(cpu):
                return f(*a)
        return g
    return on_cpu(split), on_cpu(cat), on_cpu(key)


class RefPool:
    """Reference pool state. Leaves mirror the program's ``Pool``."""

    def __init__(self, cfg: dict, rates_table: np.ndarray, seed: int,
                 break_shadow: bool = False, break_mcache: bool = False):
        for flag in ("coloc", "compact", "zero_elision", "shadow"):
            assert cfg[flag], f"reference covers {flag}=True only"
        assert not cfg["store_payload"] and cfg["demote_cadence"] == "window"
        self.cfg = cfg
        self.break_shadow = break_shadow
        self.break_mcache = break_mcache
        n_pages, n_p, n_c = cfg["n_pages"], cfg["n_pchunks"], cfg["n_cchunks"]
        self.blocks = cfg["page_bytes"] // cfg["block_bytes"]
        vals_per_block = cfg["block_bytes"] // 2
        q = cfg["quantum_bytes"]
        self.qt = (0, -(-(4 + vals_per_block // 2) // q),
                   -(-(4 + vals_per_block) // q), (2 * vals_per_block) // q)
        self.qpc = cfg["chunk_bytes"] // q
        self.q64 = q // 64
        self.blk64 = cfg["block_bytes"] // 64
        self.page64 = cfg["page_bytes"] // 64
        self.chunk64 = cfg["chunk_bytes"] // 64
        self.meta = np.zeros((n_pages, 8), np.uint32)
        self.activity = np.zeros((n_p,), np.uint32)
        self.hand = 0
        n_single = (n_c * 7 // 8) // 8 * 8
        n_groups = (n_c - n_single) // 8
        self.cfree = [np.arange(n_single, dtype=np.int32), n_single]
        self.gfree = [n_single + 8 * np.arange(n_groups, dtype=np.int32),
                      n_groups]
        self.pfree = [np.arange(n_p, dtype=np.int32), n_p]
        self.sets, self.ways = cfg["mcache_sets"], cfg["mcache_ways"]
        self.tags = np.full((self.sets, self.ways), -1, np.int32)
        self.age = np.tile(np.arange(self.ways, dtype=np.int32),
                           (self.sets, 1))
        self.c = [0] * N_COUNTERS
        self.rates = np.asarray(rates_table, np.int32)
        self._split, self._cat, keyfn = _jax_random()
        self.rng = np.asarray(keyfn(seed), np.uint32)

    # -- leaves in the program's order ---------------------------------------

    def leaves(self) -> dict:
        return {
            "meta": self.meta, "activity": self.activity,
            "hand": np.int32(self.hand),
            "cfree.items": self.cfree[0], "cfree.top": np.int32(self.cfree[1]),
            "gfree.items": self.gfree[0], "gfree.top": np.int32(self.gfree[1]),
            "pfree.items": self.pfree[0], "pfree.top": np.int32(self.pfree[1]),
            "cache.tags": self.tags, "cache.age": self.age,
            "counters": np.asarray(self.c, np.int64),
            "rng": self.rng,
        }

    # -- free lists (array stacks with a head register) ----------------------

    @staticmethod
    def _pop(fl):
        items, top = fl
        if top > 0:
            fl[1] = top - 1
            return int(items[top - 1])
        return -1

    @staticmethod
    def _push(fl, idx):
        if idx < 0:
            return
        items, top = fl
        items[min(max(top, 0), items.shape[0] - 1)] = idx
        fl[1] = top + 1

    # -- helpers ----------------------------------------------------------------

    def _ptr(self, ospn, slot):
        return int(self.meta[ospn, 1 + slot]) & PTR_MASK

    def _chunks_for(self, rates):
        quanta = sum(self.qt[r] for r in rates)
        return -(-quanta // self.qpc)

    def _header(self, rates):
        w = 0
        for i, r in enumerate(rates):
            w = _set(w, 5 * i, 2, RATE_TO_BT[r])
            w = _set(w, 5 * i + 2, 3, RATE_TO_SZ[r])
        return _set(w, 31, 1, 1)

    def _rates_of(self, w):
        return [0 if bt(w, i) == BT_ZERO else SZ_TO_RATE[sz(w, i)]
                for i in range(self.blocks)]

    def _set_index(self, ospn):
        x = ospn & U32
        return (x ^ (x >> 13)) % self.sets

    def _probe(self, ospn):
        return bool(np.any(self.tags[self._set_index(ospn)] == ospn))

    def _alloc(self, n):
        if n >= 8:
            base = self._pop(self.gfree)
            return [base] + [-1] * 6
        ptrs = [-1] * 7
        for i in range(min(n, 7)):
            ptrs[i] = self._pop(self.cfree)
        return ptrs

    def _free(self, entry):
        n = nchunks_of(int(entry[0]))
        if n == 0:
            return
        ptrs = [int(entry[1 + i]) & PTR_MASK for i in range(7)]
        if n == 8:
            self._push(self.gfree, ptrs[0])
        else:
            for i in range(7):
                self._push(self.cfree, ptrs[i] if i < n else -1)

    def _split2(self, key):
        out = np.asarray(self._split(key), np.uint32)
        return out[0], out[1]

    # -- demotion: the second-chance clock (§4.4) -----------------------------

    def _clock(self, rng, force, max_groups=8):
        n_groups = self.activity.shape[0] // GROUP
        groups, victim, used_rnd = 0, -1, False
        while groups < max_groups:
            start = ((self.hand // GROUP) % n_groups) * GROUP
            ents = self.activity[start:start + GROUP].astype(np.int64)
            alloc = (ents & ACT_ALLOC) != 0
            ref = (ents & ACT_REF) != 0
            probed = np.array([self._probe(int(o))
                               for o in (ents & ACT_OSPN)])
            eligible = alloc & ~ref & ~probed
            rnd_pool = alloc & (~probed | force)
            rng, sub = self._split2(rng)
            got = bool(eligible.any() or rnd_pool.any())
            if eligible.any():
                pick, used_rnd = int(np.argmax(eligible)), False
            else:
                used_rnd = bool(rnd_pool.any())
                pick = int(self._cat(sub, rnd_pool)) if used_rnd else 0
            victim = start + pick if got else -1
            self.activity[start:start + GROUP] = np.where(
                alloc, ents & ~ACT_REF, ents).astype(np.uint32)
            self.hand += GROUP
            groups += 1
            if got:
                break
        ospn = int(self.activity[victim]) & ACT_OSPN if victim >= 0 else -1
        return ospn, used_rnd, groups

    def _demote_one(self, force=False):
        self.rng, sub = self._split2(self.rng)
        ospn, used_rnd, groups = self._clock(sub, force)
        self.c[ACT_RD] += groups
        self.c[ACT_WR] += groups
        if used_rnd:
            self.c[RANDOM_FB] += 1
        if ospn < 0:
            return
        entry = self.meta[ospn].copy()
        w0 = int(entry[0])
        clean = _field(w0, 29, 1) == 0 and _field(w0, 28, 1) == 1
        if clean and not self.break_shadow:
            w = w0
            for i in range(self.blocks):
                restored = BT_INCOMP if sz(w, i) == 7 else BT_COMP
                if bt(w, i) == BT_PROM:
                    w = _set(w, 5 * i, 2, restored)
            w = _set(_set(w, 30, 1, 0), 28, 1, 0)
            self.meta[ospn, 0] = w
            self.c[META_WR] += 1
            self.c[DEMO_CLEAN] += 1
        else:
            rates = [int(r) for r in self.rates[ospn]]
            n = self._chunks_for(rates)
            ptrs = self._alloc(n)
            new = np.zeros((8,), np.uint32)
            new[0] = _set(self._header(rates), 20, 4, n)
            for i in range(7):
                new[1 + i] = max(ptrs[i], 0) & PTR_MASK
            self.c[DEMO_RD] += self.page64
            self.c[DEMO_WR] += n * self.chunk64
            self.c[META_WR] += 1
            self.c[DEMO_DIRTY] += 1
            self.meta[ospn] = new
        pidx = int(entry[1 + PSLOT]) & PTR_MASK
        self._push(self.pfree, pidx)
        self.activity[pidx] = 0

    def _ensure_free_pchunk(self, tries=4):
        for i in range(tries):
            if self.pfree[1] == 0:
                self._demote_one(force=i >= tries // 2)

    # -- promotion (§4.1, §4.5, §4.6) ----------------------------------------

    def _promote(self, ospn, blk):
        already = _field(int(self.meta[ospn, 0]), 30, 1) == 1
        if not already:
            self._ensure_free_pchunk()
        entry = self.meta[ospn].copy()
        w0 = int(entry[0])
        n = nchunks_of(w0)
        if already:
            pidx = int(entry[1 + PSLOT]) & PTR_MASK
        else:
            pidx = self._pop(self.pfree)
        can_shadow = n <= 6 or n == 8
        full = not can_shadow
        rates = self._rates_of(w0)
        if full:
            rd = sum(self.qt[r] for r in rates) * self.q64
        else:
            rd = self.qt[rates[min(blk, self.blocks - 1)]] * self.q64
        self.c[PROMO_RD] += rd
        self.c[PROMO_WR] += self.page64 if full else self.blk64
        self.c[PROMOTIONS] += 1
        w = w0
        for i in range(self.blocks):
            if (blk == i or full) and bt(w, i) != BT_ZERO:
                w = _set(w, 5 * i, 2, BT_PROM)
        w = _set(w, 30, 1, 1)
        w = _set(w, 28, 1, int(can_shadow))
        w = _set(w, 29, 1, int(not can_shadow))
        if not (can_shadow or n == 0):
            self._free(entry)
        w = _set(w, 20, 4, n if can_shadow else 0)
        entry[0] = w
        entry[1 + PSLOT] = max(pidx, 0) & PTR_MASK
        self.c[META_WR] += 1
        self.meta[ospn] = entry
        if not already:
            self.activity[pidx] = ACT_ALLOC | ACT_REF | (ospn & ACT_OSPN)

    # -- host-facing bodies ---------------------------------------------------

    def _write_page(self, ospn):
        was_promoted0 = _field(int(self.meta[ospn, 0]), 30, 1) == 1
        if not was_promoted0:
            self._ensure_free_pchunk()
        entry = self.meta[ospn].copy()
        self._free(entry)
        was_promoted = _field(int(entry[0]), 30, 1) == 1
        if was_promoted:
            pidx = int(entry[1 + PSLOT]) & PTR_MASK
        else:
            pidx = self._pop(self.pfree)
        w = 0
        for i in range(self.blocks):
            w = _set(w, 5 * i, 2, BT_PROM)
        w = _set(_set(_set(w, 31, 1, 1), 30, 1, 1), 29, 1, 1)
        new = np.zeros((8,), np.uint32)
        new[0] = w
        new[1 + PSLOT] = max(pidx, 0) & PTR_MASK
        self.c[DATA_WR] += self.page64
        self.c[META_WR] += 1
        self.meta[ospn] = new
        self.activity[pidx] = ACT_ALLOC | ACT_REF | (ospn & ACT_OSPN)

    def _read_block(self, ospn, blk):
        w0 = int(self.meta[ospn, 0])
        valid = _field(w0, 31, 1) == 1
        promoted = _field(w0, 30, 1) == 1
        b = bt(w0, blk)
        if valid and b == BT_ZERO:
            self.c[ZERO_SERVED] += 1
        elif valid and promoted and b == BT_PROM:
            self.c[DATA_RD] += self.blk64
        elif valid:
            self._promote(ospn, blk)

    def _write_block(self, ospn, blk):
        w0 = int(self.meta[ospn, 0])
        if _field(w0, 31, 1) == 0:
            self._write_page(ospn)
            return
        promoted = _field(w0, 30, 1) == 1
        if not promoted and nchunks_of(w0) == 8:
            self._write_inplace(ospn)
            return
        if not promoted:
            self._promote(ospn, blk)
        e = self.meta[ospn].copy()
        ww = int(e[0])
        if any(bt(ww, i) not in (BT_PROM, BT_ZERO)
               for i in range(self.blocks)):
            rates = self._rates_of(ww)
            self.c[PROMO_RD] += sum(self.qt[r] for r in rates) * self.q64
            self.c[PROMO_WR] += self.page64
        if nchunks_of(ww) > 0:
            self._free(e)
        for i in range(self.blocks):
            ww = _set(ww, 5 * i, 2, BT_PROM)
        ww = _set(_set(_set(ww, 20, 4, 0), 28, 1, 0), 29, 1, 1)
        e[0] = ww
        e[1:1 + 6] = 0
        self.meta[ospn] = e
        self.c[DATA_WR] += self.blk64
        self.c[META_WR] += 1

    def _write_inplace(self, ospn):
        self.c[DATA_WR] += self.blk64
        w0 = int(self.meta[ospn, 0])
        cntr = _field(w0, 24, 4)
        if cntr + 1 < self.cfg["wr_thresh"]:
            self.meta[ospn, 0] = _set(w0, 24, 4, cntr + 1)
            self.c[META_WR] += 1
            return
        rates = [int(r) for r in self.rates[ospn]]
        n = self._chunks_for(rates)
        self.c[DEMO_RD] += self.page64
        self.c[RECOMP_RETRY] += 1
        if n < 8:
            self._free(self.meta[ospn].copy())
            ptrs = self._alloc(n)
            new = np.zeros((8,), np.uint32)
            new[0] = _set(self._header(rates), 20, 4, n)
            for i in range(7):
                new[1 + i] = max(ptrs[i], 0) & PTR_MASK
            self.c[DEMO_WR] += n * self.chunk64
            self.c[META_WR] += 1
            self.meta[ospn] = new
        else:
            self.meta[ospn, 0] = _set(w0, 24, 4, 0)

    # -- the window-granular metadata cache ------------------------------------

    def _mcache_window(self, ospns):
        w = len(ospns)
        hits = []
        seen = set()
        for o in ospns:
            hits.append(o in seen or self._probe(o))
            seen.add(o)
        last = {}
        for i, o in enumerate(ospns):
            last[o] = i
        ways = self.ways // 2 if self.break_mcache else self.ways
        evicted = []
        touched_sets = {}
        for o, i in last.items():
            touched_sets.setdefault(self._set_index(o), []).append((-(i + 1), o))
        for s, touches in touched_sets.items():
            window_tags = {o for _, o in touches}
            cands = [(int(self.age[s, k]), int(self.tags[s, k]))
                     for k in range(self.ways)
                     if self.tags[s, k] >= 0
                     and int(self.tags[s, k]) not in window_tags]
            ranked = sorted(touches + cands)
            kept = ranked[:ways]
            evicted += [o for _, o in ranked[ways:]]
            self.tags[s] = [o for _, o in kept] + [-1] * (self.ways - len(kept))
        self.age[:] = np.arange(self.ways, dtype=np.int32)
        n_hit = sum(hits)
        self.c[MC_HIT] += n_hit
        self.c[MC_MISS] += w - n_hit
        self.c[META_RD] += w - n_hit
        flips = []
        for o in evicted:
            w0 = int(self.meta[o, 0])
            if _field(w0, 30, 1) == 1 and _field(w0, 31, 1) == 1:
                pidx = min(max(int(self.meta[o, 1 + PSLOT]) & PTR_MASK, 0),
                           self.activity.shape[0] - 1)
                if not (int(self.activity[pidx]) & ACT_REF):
                    flips.append(pidx)
        for pidx in flips:
            self.activity[pidx] = int(self.activity[pidx]) | ACT_REF
        self.c[ACT_WR] += len(flips)

    # -- one window of the batched replay --------------------------------------

    def window(self, ospns, writes, blocks):
        cfg = self.cfg
        W = len(ospns)
        extra = min(W // 4, max(2, cfg["demote_watermark"] // 2))
        target = cfg["demote_watermark"] + extra
        for _ in range(max(4, W // 4)):
            if self.pfree[1] < target:
                self._demote_one()
        fast = []
        cls = []
        slow_pages = set()
        for o, wr, b in zip(ospns, writes, blocks):
            w0 = int(self.meta[o, 0])
            valid = _field(w0, 31, 1) == 1
            promoted = _field(w0, 30, 1) == 1
            bb = bt(w0, b)
            is_zero = valid and bb == BT_ZERO
            is_hot = valid and promoted and bb == BT_PROM
            if wr:
                cand = (valid and promoted and _field(w0, 29, 1) == 1
                        and nchunks_of(w0) == 0
                        and all(bt(w0, i) == BT_PROM
                                for i in range(self.blocks)))
            else:
                cand = is_zero or is_hot or not valid
            f = cand and o not in slow_pages
            if not cand:
                slow_pages.add(o)
            fast.append(f)
            cls.append((is_zero, is_hot))
        self._mcache_window(ospns)
        for f, wr, (is_zero, is_hot) in zip(fast, writes, cls):
            if not f:
                continue
            if wr:
                self.c[HOST_WR] += 1
                self.c[DATA_WR] += self.blk64
                self.c[META_WR] += 1
            else:
                self.c[HOST_RD] += 1
                if is_zero:
                    self.c[ZERO_SERVED] += 1
                if is_hot:
                    self.c[DATA_RD] += self.blk64
        for f, o, wr, b in zip(fast, ospns, writes, blocks):
            if f:
                continue
            if wr:
                self.c[HOST_WR] += 1
                self._write_block(o, b)
            else:
                self.c[HOST_RD] += 1
                self._read_block(o, b)

    def replay(self, ospns, writes, blocks, window: int):
        ospns = [int(x) for x in ospns]
        writes = [bool(x) for x in writes]
        blocks = [int(x) for x in blocks]
        assert len(ospns) % window == 0
        for s in range(0, len(ospns), window):
            self.window(ospns[s:s + window], writes[s:s + window],
                        blocks[s:s + window])

    def zero_counters(self):
        self.c = [0] * N_COUNTERS


def program_leaves(pool) -> dict:
    """The program's pool as host arrays under the reference's leaf names."""
    import jax
    p = jax.device_get(pool)
    return {
        "meta": p.meta, "activity": p.activity, "hand": p.hand,
        "cfree.items": p.cfree.items, "cfree.top": p.cfree.top,
        "gfree.items": p.gfree.items, "gfree.top": p.gfree.top,
        "pfree.items": p.pfree.items, "pfree.top": p.pfree.top,
        "cache.tags": p.cache.tags, "cache.age": p.cache.age,
        "counters": p.counters, "rng": p.rng,
    }


def count_differences(a: dict, b: dict) -> int:
    """Elements that differ between two leaf sets (a missing or reshaped
    leaf counts as wholly different)."""
    n = 0
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b.get(k))
        if x.shape != y.shape:
            n += max(x.size, 1)
        else:
            n += int(np.sum(x.astype(np.int64) != y.astype(np.int64)))
    return n
