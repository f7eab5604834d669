"""Integration + property tests for the IBEX pool state machine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dev dependency; see requirements-dev.txt
    HAVE_HYPOTHESIS = False

from repro.common.types import PoolConfig, replace
from repro.core import engine as E

POL = E.DEFAULT_POLICY
from helpers import check_pool_invariants

CFG = PoolConfig(n_pages=64, n_cchunks=512, n_pchunks=32, mcache_sets=4,
                 mcache_ways=4, demote_watermark=4, store_payload=True)
KEY = jax.random.PRNGKey(1)


def _page(i, scale=0.1):
    return (jax.random.normal(jax.random.fold_in(KEY, i),
                              (CFG.vals_per_page,)) * scale).astype(jnp.bfloat16)


@pytest.fixture(scope="module")
def warm_pool():
    pool = E.make_pool(CFG)
    for i in range(48):
        pool = E.host_write_page(pool, CFG, POL, jnp.asarray(i), _page(i))
    return pool


def test_write_read_cycle(warm_pool):
    pool = warm_pool
    for i in range(48):
        pool, vals = E.host_read_block(pool, CFG, POL, jnp.asarray(i), jnp.asarray(0))
        ref = np.asarray(_page(i)[:CFG.vals_per_block], np.float32)
        got = np.asarray(vals, np.float32)
        assert np.abs(got - ref).max() <= CFG.tol4 * np.abs(ref).max() + 1e-6, i
    check_pool_invariants(pool, CFG)


def test_shadowed_promotion_clean_demotions(warm_pool):
    """Read-only traffic after the warmup must produce clean demotions
    (§4.5: no recompression for unmodified pages)."""
    pool = warm_pool
    base = E.counters_dict(pool)
    for rep in range(2):
        for i in range(48):
            pool, _ = E.host_read_block(pool, CFG, POL, jnp.asarray(i), jnp.asarray(rep))
    c = E.counters_dict(pool)
    clean = c["demotions_clean"] - base["demotions_clean"]
    dirty = c["demotions_dirty"] - base["demotions_dirty"]
    # every page demoted in the read phase was re-promoted from its shadow at
    # some point; dirty demotions only happen for pages still carrying their
    # first-touch (never-compressed) state.
    assert clean > 0
    assert clean >= dirty
    check_pool_invariants(pool, CFG)


def test_zero_page_elision():
    pool = E.make_pool(CFG)
    pool = E.host_write_page(pool, CFG, POL, jnp.asarray(0), jnp.zeros((CFG.vals_per_page,), jnp.bfloat16))
    # force demotion so the zero page gets compressed (to nothing)
    for i in range(1, 40):
        pool = E.host_write_page(pool, CFG, POL, jnp.asarray(i), _page(i))
    before = E.counters_dict(pool)
    pool, vals = E.host_read_block(pool, CFG, POL, jnp.asarray(0), jnp.asarray(0))
    after = E.counters_dict(pool)
    assert jnp.all(vals == 0)
    if after["zero_served"] > before["zero_served"]:
        # zero pages are served from metadata alone: no data traffic
        assert after["data_rd"] == before["data_rd"]
        assert after["promo_rd"] == before["promo_rd"]
    check_pool_invariants(pool, CFG)


@pytest.mark.slow
def test_read_your_writes(warm_pool):
    pool = warm_pool
    for i in range(6):
        blk = (jax.random.normal(jax.random.fold_in(KEY, 999 + i),
                                 (CFG.vals_per_block,)) * 0.3).astype(jnp.bfloat16)
        pool = E.host_write_block(pool, CFG, POL, jnp.asarray(i), jnp.asarray(2), blk)
        pool, rb = E.host_read_block(pool, CFG, POL, jnp.asarray(i), jnp.asarray(2))
        assert jnp.all(rb == blk)
        # I5 extended: the *other* blocks survive the write
        pool, other = E.host_read_block(pool, CFG, POL, jnp.asarray(i), jnp.asarray(0))
        ref = np.asarray(_page(i)[:CFG.vals_per_block], np.float32)
        got = np.asarray(other, np.float32)
        assert np.abs(got - ref).max() <= CFG.tol4 * np.abs(ref).max() + 1e-6
    check_pool_invariants(pool, CFG)


def test_write_invalidates_shadow(warm_pool):
    pool = warm_pool
    blk = jnp.ones((CFG.vals_per_block,), jnp.bfloat16)
    pool = E.host_write_block(pool, CFG, POL, jnp.asarray(3), jnp.asarray(1), blk)
    w0 = int(np.asarray(pool.meta)[3, 0])
    assert (w0 >> 29) & 1 == 1      # dirty
    assert (w0 >> 28) & 1 == 0      # shadow dropped
    assert (w0 >> 20) & 0xF == 0    # chunks released (the §4.5 update moment)
    check_pool_invariants(pool, CFG)


def test_compression_ratio_sane(warm_pool):
    r = float(E.compression_ratio(warm_pool, CFG))
    assert 0.9 < r < 4.0


@pytest.mark.slow
def test_shadow_disabled_all_dirty():
    cfg = replace(CFG, shadow=False)
    pool = E.make_pool(cfg)
    for i in range(48):
        pool = E.host_write_page(pool, cfg, POL, jnp.asarray(i), _page(i))
    for rep in range(2):
        for i in range(48):
            pool, _ = E.host_read_block(pool, cfg, POL, jnp.asarray(i), jnp.asarray(0))
    c = E.counters_dict(pool)
    assert c["demotions_clean"] == 0          # no shadow -> every demotion recompresses
    assert c["demotions_dirty"] > 0
    check_pool_invariants(pool, cfg)


def _random_ops_invariants(ops):
    """I1-I5 hold under arbitrary interleavings of page writes, block reads
    and block writes."""
    cfg = PoolConfig(n_pages=24, n_cchunks=256, n_pchunks=16, mcache_sets=2,
                     mcache_ways=2, demote_watermark=2, store_payload=True)
    pool = E.make_pool(cfg)
    shadow = {}  # ospn -> np page (oracle, exact for raw/zero; quantized else)
    for kind, ospn, blk, seed in ops:
        if kind == "wp":
            vals = (jax.random.normal(jax.random.PRNGKey(seed),
                                      (cfg.vals_per_page,)) * 0.1).astype(jnp.bfloat16)
            pool = E.host_write_page(pool, cfg, POL, jnp.asarray(ospn), vals)
            shadow[ospn] = np.asarray(vals, np.float32)
        elif kind == "rb":
            pool, vals = E.host_read_block(pool, cfg, POL, jnp.asarray(ospn), jnp.asarray(blk))
            if ospn in shadow:
                ref = shadow[ospn][blk * cfg.vals_per_block:(blk + 1) * cfg.vals_per_block]
                got = np.asarray(vals, np.float32)
                # 2.5x: re-quantization across demote/promote cycles can
                # compound slightly when block amax drifts on the grid
                tol = 2.5 * cfg.tol4 * max(np.abs(ref).max(), 1e-6) + 1e-6
                assert np.abs(got - ref).max() <= tol
            else:
                assert np.all(np.asarray(vals) == 0)
        else:
            bvals = (jax.random.normal(jax.random.PRNGKey(seed),
                                       (cfg.vals_per_block,)) * 0.2).astype(jnp.bfloat16)
            pool = E.host_write_block(pool, cfg, POL, jnp.asarray(ospn), jnp.asarray(blk), bvals)
            if ospn not in shadow:
                shadow[ospn] = np.zeros((cfg.vals_per_page,), np.float32)
            shadow[ospn][blk * cfg.vals_per_block:(blk + 1) * cfg.vals_per_block] = \
                np.asarray(bvals, np.float32)
    check_pool_invariants(pool, cfg)


if HAVE_HYPOTHESIS:
    OPS = st.lists(
        st.tuples(st.sampled_from(["wp", "rb", "wb"]), st.integers(0, 23),
                  st.integers(0, 3), st.integers(0, 2 ** 16)),
        min_size=5, max_size=40)

    @pytest.mark.slow
    @settings(max_examples=12, deadline=None)
    @given(ops=OPS)
    def test_property_invariants_random_ops(ops):
        _random_ops_invariants(ops)
else:
    @pytest.mark.slow
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_invariants_random_ops():
        pass


# -- replay pinned bit for bit; no pool-sized copies in the window scan --------

PIN_PAGES = 4096


def _pin_cfg(policy, fused_demote="auto"):
    """A quarter of the pages promoted, as the simulator sizes a pool; the
    compressed region is sized so that every pool-sized leaf has a shape of
    its own (meta u32[4096,8], activity u32[1024], cfree s32[43008], gfree
    s32[768], pfree s32[1024], rates_table s32[4096,4])."""
    return PoolConfig(n_pages=PIN_PAGES, n_pchunks=PIN_PAGES // 4,
                      n_cchunks=12 * PIN_PAGES, mcache_sets=128,
                      mcache_ways=16, demote_watermark=8,
                      shadow=policy.shadow, coloc=policy.coloc,
                      compact=policy.compact,
                      zero_elision=policy.zero_elision, store_payload=False,
                      fused_demote=fused_demote)


def _pin_replay(name, window, fused_demote):
    """First-touch population of Table 2 mcf's footprint, then 4,096 mcf
    accesses, through ``replay_trace`` (window 32) or the serial scan."""
    import hashlib
    from repro.core.engine import batch as B
    from repro.simx.trace import WORKLOADS, make_rates_table, make_trace
    policy = E.POLICIES[name]
    cfg = _pin_cfg(policy, fused_demote)
    spec = WORKLOADS["mcf"]
    n_used = int(cfg.n_pchunks * spec.footprint_pages)
    rates = make_rates_table(spec, PIN_PAGES, seed=7)
    ospn, wr, blk = make_trace(spec, n_accesses=4096, n_pages=n_used, seed=7)
    order = np.random.default_rng(7).permutation(n_used).astype(np.int32)
    order = order[np.arange(4096) % n_used]
    pool = E.make_pool(cfg, seed=7, rates_table=jnp.asarray(rates))
    pool = B.replay_trace(pool, cfg, policy, order, np.ones(4096, bool),
                          np.zeros(4096, np.int32), window=window)
    pool = B.replay_trace(pool, cfg, policy, ospn, wr, blk, window=window)
    leaves = jax.tree_util.tree_flatten_with_path(pool)[0]
    digests = {jax.tree_util.keystr(k):
               hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
               for k, v in leaves}
    return [int(c) for c in pool.counters], digests


# Counters and leaf digests of the replay above, recorded before the pool's
# transitions were rebuilt as write-set transactions; every later change of
# the mechanism must reproduce them bit for bit.
PINNED = {
    ('ibex', 32, 'auto'): (
        [4328, 10674, 42944, 198096, 62600, 129152, 229568, 106808, 71229,
         71778, 189, 3658, 112, 3587, 2274, 3491, 4701, 3864, 4328, 0],
        {
         '.meta': 'dc84b771ab6779e1',
         '.activity': '84540b12d7a9bb83',
         '.hand': '84b3f1bd62313a43',
         '.cfree.items': 'b6afb43b2f87f108',
         '.cfree.top': 'be3f929beb7e27e4',
         '.gfree.items': '7ca1025575bb6a3d',
         '.gfree.top': '003c69c31530c8a1',
         '.pfree.items': '0d7cbb34d2719fad',
         '.pfree.top': 'df3f619804a92fdb',
         '.cache.tags': 'f939e555fd8e8b0a',
         '.cache.age': '4f6a7fc8b9879796',
         '.counters': '96f29095179857b3',
         '.rng': '66f251f1acd326cd',
         '.c_store': 'e3b0c44298fc1c14',
         '.p_store': 'e3b0c44298fc1c14',
         '.rates_table': 'dd64a3e6f985db08',
        }),
    ('ibex', 32, 'on'): (
        [4328, 10674, 42944, 198096, 62600, 129152, 229568, 106808, 71229,
         71778, 189, 3658, 112, 3587, 2274, 3491, 4701, 3864, 4328, 0],
        {
         '.meta': 'dc84b771ab6779e1',
         '.activity': '84540b12d7a9bb83',
         '.hand': '84b3f1bd62313a43',
         '.cfree.items': 'b6afb43b2f87f108',
         '.cfree.top': 'be3f929beb7e27e4',
         '.gfree.items': '7ca1025575bb6a3d',
         '.gfree.top': '003c69c31530c8a1',
         '.pfree.items': '0d7cbb34d2719fad',
         '.pfree.top': 'df3f619804a92fdb',
         '.cache.tags': 'f939e555fd8e8b0a',
         '.cache.age': '4f6a7fc8b9879796',
         '.counters': '96f29095179857b3',
         '.rng': '66f251f1acd326cd',
         '.c_store': 'e3b0c44298fc1c14',
         '.p_store': 'e3b0c44298fc1c14',
         '.rates_table': 'dd64a3e6f985db08',
        }),
    ('ibex', 1, 'auto'): (
        [4330, 10673, 43280, 198096, 62730, 129344, 229440, 106488, 169583,
         170128, 173, 3686, 113, 3585, 2274, 3491, 4701, 3862, 4330, 0],
        {
         '.meta': '9d2855bd3bf7b2a4',
         '.activity': '0eef405a453138a2',
         '.hand': 'cbc58915d3a9ba3a',
         '.cfree.items': '557933c9ccc1b161',
         '.cfree.top': 'bcd3465a3e43aa70',
         '.gfree.items': '7ca1025575bb6a3d',
         '.gfree.top': '003c69c31530c8a1',
         '.pfree.items': 'c123b02aa690d8b6',
         '.pfree.top': 'df3f619804a92fdb',
         '.cache.tags': 'ab120fbe002b94e1',
         '.cache.age': '696e4427eee2853a',
         '.counters': '4e818bac2f3047f6',
         '.rng': '88231c2132691e32',
         '.c_store': 'e3b0c44298fc1c14',
         '.p_store': 'e3b0c44298fc1c14',
         '.rates_table': 'dd64a3e6f985db08',
        }),
    ('dylect', 32, 'auto'): (
        [8656, 15557, 44896, 198096, 51056, 99968, 198272, 126482, 58604,
         67520, 102, 3041, 0, 3098, 1562, 3491, 4701, 3864, 4328, 0],
        {
         '.meta': 'd735aac807a0afe1',
         '.activity': '863c3092b58a572e',
         '.hand': 'd89e0ebbd89da18d',
         '.cfree.items': '6de937a6c0c812ca',
         '.cfree.top': '7cac4f5ee54aef1f',
         '.gfree.items': '1d126d25e313d799',
         '.gfree.top': '60f2e332c71bcda8',
         '.pfree.items': '7149aabaa211f0ad',
         '.pfree.top': 'df3f619804a92fdb',
         '.cache.tags': 'f939e555fd8e8b0a',
         '.cache.age': '4f6a7fc8b9879796',
         '.counters': '630109522adee0f2',
         '.rng': '23af357c25e9b087',
         '.c_store': 'e3b0c44298fc1c14',
         '.p_store': 'e3b0c44298fc1c14',
         '.rates_table': 'dd64a3e6f985db08',
        }),
    ('dylect', 1, 'auto'): (
        [8660, 15561, 45056, 198096, 51102, 100032, 198336, 126547, 156110,
         165007, 90, 3087, 0, 3099, 1563, 3491, 4701, 3862, 4330, 0],
        {
         '.meta': '13ab87b775bf0fdb',
         '.activity': '48c5d5c42f262cfa',
         '.hand': '825c2c9a523b4cb2',
         '.cfree.items': 'b9e18c4950d33d34',
         '.cfree.top': 'd388304e5c509e74',
         '.gfree.items': '8cd1c9c55226ce36',
         '.gfree.top': '39bb93b95ee2b56f',
         '.pfree.items': '95a9d3b5ec6ed5c4',
         '.pfree.top': 'df3f619804a92fdb',
         '.cache.tags': 'ab120fbe002b94e1',
         '.cache.age': '696e4427eee2853a',
         '.counters': '798e7d8b40ee4dbf',
         '.rng': 'a1f216298614d859',
         '.c_store': 'e3b0c44298fc1c14',
         '.p_store': 'e3b0c44298fc1c14',
         '.rates_table': 'dd64a3e6f985db08',
        }),
}


@pytest.mark.parametrize("name,window,fused", sorted(PINNED))
def test_replay_pinned(name, window, fused):
    """The batched and the serial replay, under two policies, give the
    pinned counters and every leaf bit for bit. The batched-against-serial
    tests cannot see a change that moves both paths together; this can."""
    counters, digests = _pin_replay(name, window, fused)
    want_counters, want_digests = PINNED[(name, window, fused)]
    assert counters == want_counters
    assert digests == want_digests


def _while_body_ops(hlo: str):
    """(op, shape) of every instruction in the computations that the body
    and condition of the entry computation's outermost while loop (the
    window scan) call, at any depth."""
    import re
    comps, callees, cur, entry = {}, {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = head.group(2)
            comps[cur], callees[cur] = [], []
            entry = cur if head.group(1) else entry
            continue
        if cur is None or not line.startswith("  "):
            continue
        callees[cur] += [c.lstrip("%") for ref in re.findall(
            r"(?:calls|body|condition|to_apply|branch_computations|"
            r"true_computation|false_computation)=(\{[^}]*\}|%?[\w.\-]+)",
            line) for c in ref.strip("{}").split(", ")]
        op = re.search(r"= (\w+\[[\d,]*\])\S* ([\w\-]+)\(", line)
        if op:
            comps[cur].append((op.group(2), op.group(1)))
    loop = next(line for line in hlo.split("ENTRY", 1)[1].splitlines()
                if re.search(r"\) while\(", line))
    todo = re.findall(r"(?:body|condition)=%?([\w.\-]+)", loop)
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo += callees.get(c, [])
    return [op for c in seen for op in comps.get(c, [])]


@pytest.mark.parametrize("fused", ["off", "on"])
def test_window_scan_copies_no_pool_leaf(fused):
    """The compiled window scan copies and selects no pool-sized leaf: the
    slow drain, the top-up (serial, and batched as the TPU runs it) and the
    skipped drain slots update the pool in place."""
    from repro.core.engine import batch as B
    policy = E.POLICIES["ibex"]
    cfg = _pin_cfg(policy, fused)
    pool = E.make_pool(cfg)
    big = {f"{'u' if a.dtype == jnp.uint32 else 's'}32"
           f"[{','.join(map(str, a.shape))}]"
           for a in (pool.meta, pool.activity, pool.cfree.items,
                     pool.gfree.items, pool.pfree.items, pool.rates_table)}
    assert len(big) == 6               # one shape per leaf
    idx = jnp.zeros((4, 32), jnp.int32)
    hlo = B._replay_windows.lower(pool, cfg, policy, idx,
                                  jnp.zeros((4, 32), bool),
                                  idx).compile().as_text()
    ops = _while_body_ops(hlo)
    assert any(shape == "u32[4096,8]" and op == "dynamic-update-slice"
               for op, shape in ops)   # the parser did see the drain
    bad = [(op, shape) for op, shape in ops
           if op in ("copy", "select") and shape in big]
    assert not bad, bad
