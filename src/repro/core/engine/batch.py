"""Batched access front-end (DESIGN.md §6).

Trace replay used to run one access per ``lax.scan`` step, paying the full
serial state-machine path for every access. This front-end processes a
window of W accesses per step:

  phase 0  background demotion engine tops up the free-P-chunk watermark
           once per window;
  phase 1  vectorized classification against a window-start metadata
           snapshot: accesses that resolve without metadata transitions —
           hot/zero/invalid reads, and writes to already-promoted all-hot
           dirty pages — are *fast*; their traffic is summed with window
           vector arithmetic;
  phase 2  vectorized metadata probes + activity updates: the whole window
           goes through ``mcache.access_window`` (window-granular LRU) and
           one masked scatter applies every lazy referenced-bit update;
  phase 3  conflict serialization: the remaining accesses — writes,
           promotions, and *same-page hits* whose predecessor in the window
           was itself slow — replay in order through the exact serial
           per-access bodies, looping only over the n_slow conflicts.

Fast accesses mutate nothing but counters, so a fast predecessor can never
invalidate a later classification; slow accesses re-read live metadata.
The divergences from the serial engine are (a) background-demotion timing
(per window instead of per access — ``cfg.demote_cadence="access"``
removes this one for small-pool comparisons), (b) window-granular
metadata-cache recency, and (c) a fast hot-read of a page a slow access
demoted earlier in the same window is still accounted as hot. All shift
counters within noise at sane region ratios (asserted by
tests/test_simx_schemes.py); invariants I1-I5 are unaffected
(tests/test_pool_properties.py).

``_replay_windows_masked`` is the window scan over a *padded* trace — the
multi-expander fabric (repro.fabric) vmaps it over a stacked pool state;
it reuses the window/serial bodies above unchanged so fabric counters are
bit-identical to single-pool replays of each expander's partition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.types import PoolConfig
from repro.core import mcache as mcc
from repro.core import metadata as md
from repro.core.engine import ops
from repro.core.engine.policy import Policy
from repro.core.engine.state import (C_ACT_WR, C_DATA_RD, C_DATA_WR,
                                     C_HOST_RD, C_HOST_WR, C_MC_HIT,
                                     C_MC_MISS, C_META_RD, C_META_WR,
                                     C_ZERO_SERVED, Pool, bump)
from repro.obs import spans

DEFAULT_WINDOW = 32
SLOW_FORI = 8      # slow accesses handled per window before the while loop


def _classify_window(pool: Pool, cfg: PoolConfig, ospns, writes, blocks):
    """Vectorized fast-path mask over a window (see module docstring).

    An access is *fast* when its window-start metadata snapshot resolves it
    without state transitions — a read of a hot block, zero block, or
    invalid page, or a write to an already-promoted dirty page with every
    block hot (§4.5 steady state: such a write leaves the metadata word
    bit-identical and only moves data + counters) — and no earlier access
    in the window both touched the same page and was itself slow. Fast
    accesses never mutate metadata, so a fast predecessor on the same page
    cannot invalidate the snapshot."""
    w0s = pool.meta[ospns, 0]                                  # [W]
    valid = md.get_valid(w0s) == 1
    promoted = md.get_promoted(w0s) == 1
    if cfg.coloc:
        bt = md.get_block_type_dyn(w0s, blocks)
        all_prom = jnp.ones_like(valid)
        for i in range(cfg.blocks_per_page):
            all_prom = all_prom & (md.get_block_type(w0s, i) == md.BT_PROM)
    else:
        bt = md.get_block_type(w0s, 0)
        all_prom = bt == md.BT_PROM
    is_zero = valid & (bt == md.BT_ZERO)
    is_hot = valid & promoted & (bt == md.BT_PROM)
    hot_write = valid & promoted & all_prom & \
        (md.get_dirty(w0s) == 1) & (md.get_num_chunks(w0s) == 0)
    candidate = jnp.where(writes, hot_write, is_zero | is_hot | (~valid))
    w = ospns.shape[0]
    earlier = jnp.arange(w)[None, :] < jnp.arange(w)[:, None]
    same = ospns[:, None] == ospns[None, :]
    slow_pred = jnp.any(same & earlier & (~candidate)[None, :], axis=1)
    fast = candidate & (~slow_pred)
    return fast, is_zero, is_hot


def _mcache_window(pool: Pool, cfg: PoolConfig, policy: Policy, ospns) -> Pool:
    """Vectorized metadata-cache walk + lazy activity updates for one window
    (mcache.access_window has the recency model). The ~W serial cache steps
    of the one-access-per-step engine collapse into a handful of vector ops."""
    cache, hits, evicted = mcc.access_window(pool.cache, ospns)
    n_hit = jnp.sum(hits)
    n_miss = ospns.shape[0] - n_hit
    if cfg.compact:
        widths = jnp.ones_like(ospns)
    else:
        widths = 1 + (ospns & 1)     # uncompacted entries straddle 64B (§4.7)
    counters = bump(pool.counters, C_MC_HIT, n_hit)
    counters = bump(counters, C_MC_MISS, n_miss)
    counters = bump(counters, C_META_RD, jnp.sum(jnp.where(hits, 0, widths)))
    counters = policy.on_mcache_miss(counters, n=n_miss)
    # lazy reference update (§4.4) for every eviction, as one masked scatter
    ev = evicted.reshape(-1)
    entries = pool.meta[jnp.maximum(ev, 0)]
    w0 = entries[:, 0]
    prom = (md.get_promoted(w0) == 1) & (md.get_valid(w0) == 1) & (ev >= 0)
    pidx = md.get_ptr(entries, md.PCHUNK_SLOT).astype(jnp.int32)
    safe_pidx = jnp.clip(jnp.where(prom, pidx, 0), 0,
                         pool.activity.shape[0] - 1)
    already = md.act_referenced(pool.activity[safe_pidx]) == 1
    ref_bit = jnp.uint32(1) << jnp.uint32(md.ACT_REFERENCED_BIT)
    flips = prom & (~already)
    delta = jnp.where(flips, ref_bit, jnp.uint32(0))
    activity = pool.activity.at[safe_pidx].add(delta)
    # charge exactly the activity words written: evictions whose referenced
    # bit actually flips (an already-referenced entry needs no write) —
    # matches the serial path's charge in ops.mcache_step
    counters = policy.charge_activity(counters, C_ACT_WR, jnp.sum(flips))
    return pool._replace(cache=cache, activity=activity, counters=counters)


def _window_step(pool: Pool, cfg: PoolConfig, policy: Policy, xs,
                 unroll_slow: bool = False):
    ospns, writes, blocks = xs
    window = ospns.shape[0]
    zero_block = jnp.zeros((cfg.vals_per_block,), jnp.bfloat16)

    # phase 0: background demotion engine — top up once per window to a
    # raised target (watermark + expected promotions per window) so the
    # free list rarely exhausts mid-window; a window with more promotions
    # than that stays live through the promote path's self-ensure.
    # The top-up is a while loop that stops once the target is met, each
    # demotion a write-set transition (ops.py), so it copies no pool-sized
    # state: on a v5e the former fori-of-cond copied `meta` and the free
    # lists whole on every iteration, taken or skipped.
    # the raise is bounded by the watermark so small pools keep (almost)
    # the serial engine's residency: a higher target would evict hot pages
    # the serial engine keeps resident and skew traffic at small scales.
    # cfg.demote_cadence == "access" drops the raise entirely and instead
    # re-checks the watermark before every slow access (below) — the serial
    # engine's cadence, for small pools where the raise itself skews traffic
    per_access = cfg.demote_cadence == "access"
    if per_access:
        # no raised target; the window-start top-up may fully catch up (the
        # serial engine had one demote opportunity before every one of the
        # preceding fast accesses) and every slow access re-checks below
        extra = 0
        budget = window
    else:
        extra = min(window // 4, max(2, cfg.demote_watermark // 2))
        budget = max(4, window // 4)
    with spans.scope(spans.TOPUP):
        pool = ops.demote_if_needed(pool, cfg, policy, max_demotes=budget,
                                    watermark=cfg.demote_watermark + extra)

    # phase 1: classification snapshot (phase 2 never touches metadata)
    with spans.scope(spans.CLASSIFY):
        fast, is_zero, is_hot = _classify_window(pool, cfg, ospns, writes,
                                                 blocks)

    # phase 2: vectorized metadata probes + activity updates for the window
    with spans.scope(spans.MCACHE):
        pool = _mcache_window(pool, cfg, policy, ospns)

        # vectorized accounting for the fast accesses
        fast_rd = fast & (~writes)
        fast_wr = fast & writes
        n_fast_rd = jnp.sum(fast_rd)
        n_fast_wr = jnp.sum(fast_wr)
        counters = bump(pool.counters, C_HOST_RD, n_fast_rd)
        counters = bump(counters, C_HOST_WR, n_fast_wr)
        counters = policy.on_host_access(counters, False, n=n_fast_rd)
        counters = policy.on_host_access(counters, True, n=n_fast_wr)
        counters = bump(counters, C_ZERO_SERVED, jnp.sum(fast_rd & is_zero))
        counters = bump(counters, C_DATA_RD,
                        jnp.sum(fast_rd & is_hot) * (cfg.block_bytes // 64))
        # fast (hot, dirty) writes: data write + metadata write-back, no
        # metadata *change* — see _classify_window
        counters = bump(counters, C_DATA_WR,
                        n_fast_wr * (cfg.block_bytes // 64))
        if cfg.compact:
            wr_widths = n_fast_wr
        else:
            wr_widths = jnp.sum(jnp.where(fast_wr, 1 + (ospns & 1), 0))
        counters = bump(counters, C_META_WR, wr_widths)
        pool = pool._replace(counters=counters)

    # phase 3: serialized replay of the slow accesses only — fast accesses
    # pay no per-access control flow at all. The first SLOW_FORI slow
    # accesses run in a fori whose slot k is masked by k < n_slow (a masked
    # slot commits a write list that writes back what it reads); the rare
    # overflow (a window with more slow accesses than SLOW_FORI, e.g.
    # first-touch population) drains through a while loop. A slot is a
    # write-set transition (ops.py): the v5e trace showed the former
    # per-slot cond copying the pool's `meta` and free lists whole, even
    # for a skipped slot, and those copies were most of the drain's time.
    #
    # ``unroll_slow`` replaces BOTH lax loops with a statically unrolled
    # python loop over the full window: XLA:CPU deterministically
    # miscompiles this drain when the vmapped body sits inside a
    # ``shard_map`` manual region on any device other than 0 (a window's
    # slow write replays as a read; forced host devices, jax 0.4.37 —
    # isolated by tests/test_fabric_sharded.py's bit-identity suite),
    # while the unrolled form is bit-exact there. Single-device paths
    # keep the loops: same op sequence, smaller HLO.
    with spans.scope(spans.DRAIN):
        pool = _drain_slow(pool, cfg, policy, xs, fast, zero_block,
                           unroll_slow)
    return pool, None


def _drain_slow(pool: Pool, cfg: PoolConfig, policy: Policy, xs, fast,
                zero_block, unroll_slow: bool) -> Pool:
    """Phase 3 of ``_window_step``: the window's slow accesses, in order,
    through the serial per-access bodies."""
    ospns, writes, blocks = xs
    window = ospns.shape[0]
    per_access = cfg.demote_cadence == "access"
    n_slow = jnp.sum(~fast)
    slow_order = jnp.argsort(jnp.where(fast, window + jnp.arange(window),
                                       jnp.arange(window)))

    def process(k, p: Pool, on) -> Pool:
        if per_access:
            p = ops.demote_if_needed(p, cfg, policy, on=on)
        p = p._replace(counters=ops.host_count(p.counters, policy, writes[k],
                                               on))
        return ops.access(p, cfg, policy, ospns[k], blocks[k], writes[k],
                          zero_block, on)[0]

    if unroll_slow:
        for i in range(window):
            pool = process(slow_order[i], pool, i < n_slow)
        return pool

    k_fori = min(SLOW_FORI, window)
    pool = jax.lax.fori_loop(
        0, k_fori, lambda i, p: process(slow_order[i], p, i < n_slow), pool)

    def slow_cond(carry):
        i, _ = carry
        return i < n_slow

    def slow_body(carry):
        i, p = carry
        return i + 1, process(slow_order[i], p, True)

    _, pool = jax.lax.while_loop(slow_cond, slow_body,
                                 (jnp.asarray(k_fori, jnp.int32), pool))
    return pool


@functools.partial(jax.jit, static_argnums=(1, 2))
def _replay_windows(pool: Pool, cfg: PoolConfig, policy: Policy, ospns,
                    writes, blocks) -> Pool:
    def scan_step(p, xs):
        return _window_step(p, cfg, policy, xs)

    pool, _ = jax.lax.scan(scan_step, pool, (ospns, writes, blocks))
    return pool


def _serial_access(pool: Pool, cfg: PoolConfig, policy: Policy, ospn, w, blk,
                   on=True) -> Pool:
    """One access through the serial per-access path (full prologue — the
    exact body `_replay_serial` scans and the masked window path's partial
    windows replay; sharing it is what makes the fabric's padded replay
    counter-exact against `replay_trace`). An access that is not ``on`` is
    an exact no-op."""
    zero_block = jnp.zeros((cfg.vals_per_block,), jnp.bfloat16)
    pool = ops._prologue(pool, cfg, policy, ospn, w, on)
    return ops.access(pool, cfg, policy, ospn, blk, w, zero_block, on)[0]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _replay_serial(pool: Pool, cfg: PoolConfig, policy: Policy, ospns,
                   writes, blocks, valid=None) -> Pool:
    """The seed's one-access-per-step scan (kept as the batched path's
    reference and for BENCH_simx.json before/after measurements).

    ``valid=None`` processes every access. A bool mask makes masked-out
    accesses exact no-ops (pool and counters untouched) — the batched path
    pads its trace tail with them so every tail compiles at one shape."""
    if valid is None:
        valid = jnp.ones(ospns.shape, bool)

    def step(p, x):
        return _serial_access(p, cfg, policy, *x), None

    pool, _ = jax.lax.scan(step, pool, (ospns, writes, blocks, valid))
    return pool


def _replay_windows_masked(pool: Pool, cfg: PoolConfig, policy: Policy,
                           ospns, writes, blocks, valid,
                           pending=None, unroll_slow: bool = False) -> Pool:
    """Window scan over a *padded* trace: the multi-expander fabric's entry
    point (fabric/replay.py vmaps it over a stacked pool state).

    Each expander's trace partition is a prefix of real accesses followed by
    padding, reshaped to [n_win, W] with a bool validity mask. Per window:

      * all-valid   -> the exact `_window_step` body (same as
                       `_replay_windows`);
      * part-valid  -> the serial per-access body over the valid prefix
                       (same as `replay_trace`'s padded serial tail);
      * none-valid  -> exact no-op.

    Padding sits at the end, so a padded replay walks full windows then one
    partial window then no-ops — the very shapes `replay_trace` produces —
    and its counters are bit-identical to an unpadded `replay_trace` of the
    real prefix (asserted by tests/test_fabric.py). Under `vmap` the
    three-way branch lowers to selects, so every expander pays the heavier
    body's cost; fabric throughput numbers carry that constant honestly
    (benchmarks/fabric_bench.py).

    ``unroll_slow`` is forwarded to ``_window_step``: the sharded fabric
    passes True because XLA:CPU miscompiles the fori/while slow-access
    drain inside ``shard_map`` manual regions (see ``_window_step``).

    ``pending`` is the fabric scheduler's carried pending-migration mask
    (bool[n_pages], shared across expanders): accesses to pages whose
    migration plan is in flight are masked to exact no-ops mid-segment —
    the host defers and replays them after the epoch commits, routed to
    the page's final home — so an in-flight page is never touched by a
    replay racing its own migration. An all-False mask reduces to
    ``valid`` unchanged (identical numerics to ``pending=None``: the
    fabric's parity contract survives the overlap machinery)."""
    def scan_step(p, xs):
        o, w, b, v = xs
        if pending is not None:
            v = v & ~pending[o]

        def none_valid(q: Pool) -> Pool:
            return q

        def part_valid(q: Pool) -> Pool:
            def step(q2, x):
                return _serial_access(q2, cfg, policy, *x), None
            q, _ = jax.lax.scan(step, q, (o, w, b, v))
            return q

        def all_valid(q: Pool) -> Pool:
            return _window_step(q, cfg, policy, (o, w, b),
                                unroll_slow=unroll_slow)[0]

        branch = jnp.where(jnp.all(v), 2,
                           jnp.where(jnp.any(v), 1, 0)).astype(jnp.int32)
        return jax.lax.switch(branch, [none_valid, part_valid, all_valid],
                              p), None

    pool, _ = jax.lax.scan(scan_step, pool,
                           (ospns, writes, blocks, valid))
    return pool


def replay_trace(pool: Pool, cfg: PoolConfig, policy: Policy, ospns, writes,
                 blocks, *, window: int = DEFAULT_WINDOW) -> Pool:
    """Replay a (ospn, is_write, block) trace through the pool.

    ``window > 1`` uses the batched front-end; ``window <= 1`` runs the
    serial scan over the whole trace. The trace tail that does not fill a
    window (and any trace shorter than one window) replays serially, padded
    to exactly ``window`` accesses with masked no-ops — so the batched path
    compiles a fixed set of shapes (the window scan plus one window-sized
    serial tail) no matter the trace length, instead of one ``_replay_serial``
    per distinct tail length. Write accesses carry a zero-block payload
    (trace replay measures traffic, not data)."""
    ospns = jnp.asarray(ospns, jnp.int32)
    writes = jnp.asarray(writes, bool)
    blocks = jnp.asarray(blocks, jnp.int32)
    n = int(ospns.shape[0])
    if window <= 1:
        return _replay_serial(pool, cfg, policy, ospns, writes, blocks)
    n_win = n // window
    head = n_win * window
    if n_win:
        pool = _replay_windows(pool, cfg, policy,
                               ospns[:head].reshape(n_win, window),
                               writes[:head].reshape(n_win, window),
                               blocks[:head].reshape(n_win, window))
    tail = n - head
    if tail:
        pad = window - tail
        pz = lambda a: jnp.pad(a[head:], ((0, pad),))
        valid = jnp.arange(window) < tail
        pool = _replay_serial(pool, cfg, policy, pz(ospns), pz(writes),
                              pz(blocks), valid)
    return pool
