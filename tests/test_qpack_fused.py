"""Fused demote/promote kernel parity + batched demotion bit-identity +
measurement-calibrated device model (DESIGN.md §14).

The fused Pallas kernels must be *bit-identical* to the jnp oracle in
``core/compressor.py`` — same reciprocal-multiply quantization, same byte
layout — across all four rate codes and both block modes, so the engine can
dispatch on ``compress_impl`` without changing any pool state. Off-TPU the
kernels run in interpret mode (this is the CI kernel-parity smoke)."""
import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import PoolConfig
from repro.core import compressor as comp
from repro.core import engine as E
from repro.core import mcache as mcc
from repro.core import metadata as md
from repro.core.engine import ops as OPS
from repro.kernels import ops as kops
from repro.kernels import qpack as qp
from repro.simx import time as TM

KEY = jax.random.PRNGKey(0)
POL = E.DEFAULT_POLICY


# -- crafted blocks covering every rate under lossless selection -------------

def _blocks_all_rates(v: int, n: int) -> jnp.ndarray:
    """n blocks of v values cycling zero -> exact-4bit -> exact-8bit -> raw.

    Exact 4-bit needs integer values with amax exactly 7 (scale = 7/7 = 1.0);
    exact 8-bit: integers with amax exactly 127. Both are bf16-exact, and the
    4-bit roundtrip of the 8-bit block fails (scale 127/7 is inexact), so
    lossless selection lands each block on the intended rate."""
    blocks = []
    for i in range(n):
        k = jax.random.fold_in(KEY, i)
        m = i % 4
        if m == 0:
            b = jnp.zeros((v,), jnp.bfloat16)
        elif m == 1:
            b = jax.random.randint(k, (v,), -7, 8).astype(jnp.bfloat16)
            b = b.at[0].set(7.0)
        elif m == 2:
            b = jax.random.randint(k, (v,), -120, 121).astype(jnp.bfloat16)
            b = b.at[0].set(127.0)
        else:
            b = (jax.random.normal(k, (v,)) * 3).astype(jnp.bfloat16)
        blocks.append(b)
    return jnp.stack(blocks)


def _assert_trees_equal(a, b, what=""):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


# -- fused kernel vs jnp oracle ----------------------------------------------

@pytest.mark.parametrize("coloc", [True, False])
def test_fused_encode_decode_all_rates_bit_identical(coloc):
    cfg_j = PoolConfig(coloc=coloc, lossless=True, compress_impl="jnp")
    cfg_k = dataclasses.replace(cfg_j, compress_impl="kernel")
    nb = cfg_j.blocks_per_page if coloc else 1
    xs = _blocks_all_rates(cfg_j.vals_per_page // nb, 8 * nb) \
        .reshape(8, cfg_j.vals_per_page)
    bj, rj, qj, nj = comp.encode_pages(xs, cfg_j)
    bk, rk, qk, nk = comp.encode_pages(xs, cfg_k)
    # all four rates actually exercised
    assert set(np.asarray(rj).ravel().tolist()) == {0, 1, 2, 3}
    _assert_trees_equal((bj, rj, qj, nj), (bk, rk, qk, nk), "encode")
    dj = comp.decode_pages(bj, rj, cfg_j)
    dk = comp.decode_pages(bj, rj, cfg_k)
    np.testing.assert_array_equal(np.asarray(dj, np.float32),
                                  np.asarray(dk, np.float32))
    # lossless blocks roundtrip exactly on the kernel path too
    keep = np.asarray(rj).ravel() != 3
    got = np.asarray(dk, np.float32).reshape(8 * nb, -1)[keep]
    want = np.asarray(xs, np.float32).reshape(8 * nb, -1)[keep]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("lossless", [False, True])
@pytest.mark.parametrize("v", [128, 256])
def test_fused_encode_narrow_blocks_bit_identical(v, lossless):
    """Blocks whose value planes are narrower than a vector register (16
    and 32 lanes) still match the oracle's rates and dense rows, and the
    rate comes from the oracle's divide."""
    xs = _blocks_all_rates(v, 16)
    cfg = PoolConfig(lossless=lossless)
    rates = comp.select_rate(xs, cfg)
    dense = jax.vmap(comp._encode_block_dense)(xs, rates)
    got_d, got_r, got_q = qp.qpack_fused_encode_2d(
        xs, lossless=lossless, quanta=comp.quanta_per_rate(v),
        interpret=True)
    _assert_trees_equal((got_d, got_r), (dense, rates))
    np.testing.assert_array_equal(
        np.asarray(got_q),
        np.asarray(comp.block_quanta_table(v))[np.asarray(rates)])


def test_fused_single_page_dispatch_matches_batched():
    cfg_k = PoolConfig(lossless=True, compress_impl="kernel")
    x = _blocks_all_rates(cfg_k.vals_per_block, 4).reshape(-1)
    b1, r1, q1, n1 = comp.encode_page(x, cfg_k)
    bb, rb, qb, nb_ = comp.encode_pages(x[None], cfg_k)
    _assert_trees_equal((b1, r1, q1, n1), (bb[0], rb[0], qb[0], nb_[0]))
    d1 = comp.decode_page(b1, r1, cfg_k)
    dj = comp.decode_page(b1, r1, dataclasses.replace(cfg_k,
                                                      compress_impl="jnp"))
    np.testing.assert_array_equal(np.asarray(d1, np.float32),
                                  np.asarray(dj, np.float32))


def test_fused_default_tol_random_parity():
    cfg_j = PoolConfig(compress_impl="jnp")
    cfg_k = dataclasses.replace(cfg_j, compress_impl="kernel")
    xs = (jax.random.normal(KEY, (4, cfg_j.vals_per_page)) *
          0.7).astype(jnp.bfloat16)
    _assert_trees_equal(comp.encode_pages(xs, cfg_j),
                        comp.encode_pages(xs, cfg_k))


def test_fused_zero_elision_clamp_parity():
    cfg_j = PoolConfig(zero_elision=False, compress_impl="jnp")
    cfg_k = dataclasses.replace(cfg_j, compress_impl="kernel")
    xs = jnp.zeros((2, cfg_j.vals_per_page), jnp.bfloat16)
    out_j = comp.encode_pages(xs, cfg_j)
    out_k = comp.encode_pages(xs, cfg_k)
    # all-zero blocks are clamped to the 4-bit rate, never elided
    assert (np.asarray(out_j[1]) == 1).all()
    _assert_trees_equal(out_j, out_k)


def test_fused_quanta_match_rate_table():
    cfg_k = PoolConfig(lossless=True, compress_impl="kernel")
    xs = _blocks_all_rates(cfg_k.vals_per_block, 16) \
        .reshape(4, cfg_k.vals_per_page)
    _, rates, quanta, _ = comp.encode_pages(xs, cfg_k)
    qt = np.asarray(comp.block_quanta_table(cfg_k.vals_per_block))
    np.testing.assert_array_equal(np.asarray(quanta), qt[np.asarray(rates)])


def test_quantize_blocks_fast_parity():
    x = (jax.random.normal(KEY, (4, 1024)) * 2).astype(jnp.bfloat16)
    for bits in (4, 8):
        cj, sj = comp.quantize_blocks(x, bits, 256)
        ck, sk = comp.quantize_blocks_fast(x, bits, 256, impl="kernel")
        np.testing.assert_array_equal(np.asarray(cj), np.asarray(ck))
        np.testing.assert_array_equal(np.asarray(sj), np.asarray(sk))


@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_blocks_fast_kernel_under_jit(bits):
    """The kernel path traces under jit (the server's lane demotion is a
    jitted caller): block counts come from static shapes, never from a
    traced value."""
    x = (jax.random.normal(KEY, (2, 3, 16, 128)) * 2).astype(jnp.float32)
    fast = jax.jit(lambda a: comp.quantize_blocks_fast(a, bits, 128,
                                                       impl="kernel"))
    cj, sj = comp.quantize_blocks(x, bits, 128)
    ck, sk = fast(x)
    np.testing.assert_array_equal(np.asarray(cj), np.asarray(ck))
    np.testing.assert_array_equal(np.asarray(sj), np.asarray(sk))


def test_interpret_auto_detect():
    """Satellite 1: interpret defaults to backend detection, not True."""
    on_tpu = jax.default_backend() == "tpu"
    assert qp.resolve_interpret(None) == (not on_tpu)
    assert qp.resolve_interpret(True) is True
    assert qp.resolve_interpret(False) is False
    # the wrappers decide per call, never from a backend query at import
    assert not hasattr(kops, "INTERPRET")


def test_resolve_impl_dispatch():
    assert comp.resolve_impl(PoolConfig(compress_impl="jnp")) == "jnp"
    assert comp.resolve_impl(PoolConfig(compress_impl="kernel")) == "kernel"
    auto = comp.resolve_impl(PoolConfig())
    assert auto == ("kernel" if jax.default_backend() == "tpu" else "jnp")


# -- batched multi-victim demotion vs the serial reference -------------------

def _demotions(c):
    return c["demotions_clean"] + c["demotions_dirty"]


def _burst_pool(cfg, n_writes):
    """Oversubscribed write burst: every P-chunk allocated + dirty."""
    pool = E.make_pool(cfg)
    for i in range(n_writes):
        x = (jax.random.normal(jax.random.fold_in(KEY, i),
                               (cfg.vals_per_page,)) * 0.1).astype(jnp.bfloat16)
        pool = E.host_write_page(pool, cfg, POL, jnp.asarray(i), x)
    return pool


def _victim_ready(pool, cfg):
    """Make clock_scan victims deterministically findable: clear every
    allocated entry's referenced bit and flush the metadata cache, so the
    eligibility mask ``alloc & ~ref & ~probed`` covers the whole promoted
    region (a freshly written burst is all-referenced and cache-resident,
    which starves the non-forced demotion site)."""
    alloc = md.act_allocated(pool.activity) == 1
    cleared = jnp.where(alloc, md.act_set_referenced(pool.activity, 0),
                        pool.activity)
    return pool._replace(activity=cleared,
                         cache=mcc.make_mcache(cfg.mcache_sets,
                                               cfg.mcache_ways))


def _demote_cfg(**kw):
    # 36 written pages over 24 P-chunks: the burst exhausts the promoted
    # region, so the victim-ready pool starts at free_count(pfree) == 0
    return PoolConfig(n_pages=48, n_cchunks=384, n_pchunks=24, mcache_sets=4,
                      mcache_ways=4, demote_watermark=4, **kw)


def _demote_pair(base, n_writes=36, max_demotes=3, watermark=8,
                 ser_impl="jnp", bat_impl="jnp"):
    """One victim-ready pool through serial demote_if_needed vs demote_batch.

    Returns (input_pool, serial_out, batched_out)."""
    ser_cfg = dataclasses.replace(base, fused_demote="off",
                                  compress_impl=ser_impl)
    bat_cfg = dataclasses.replace(base, fused_demote="on",
                                  compress_impl=bat_impl)
    pool = _victim_ready(_burst_pool(ser_cfg, n_writes), base)
    run = lambda cfg: jax.jit(functools.partial(
        OPS.demote_if_needed, cfg=cfg, policy=POL, max_demotes=max_demotes,
        watermark=watermark))(pool)
    return pool, run(ser_cfg), run(bat_cfg)


def _check_pair(pool, ser, bat, max_demotes=3, what=""):
    delta = _demotions(E.counters_dict(ser)) - _demotions(E.counters_dict(pool))
    assert delta == max_demotes, \
        f"serial demote_if_needed demoted {delta}/{max_demotes} — " \
        "demote_batch not genuinely exercised"
    _assert_trees_equal(ser, bat, what)
    assert E.counters_dict(ser) == E.counters_dict(bat)


def test_batched_demote_bit_identical_payload():
    base = _demote_cfg(store_payload=True)
    pool, ser, bat = _demote_pair(base)
    _check_pair(pool, ser, bat, what="payload pools")
    # the burst leaves every written page dirty, so the batch recompressed
    # real payloads (the fused-encode path), not just clean revalidations
    assert E.counters_dict(ser)["demotions_dirty"] > \
        E.counters_dict(pool)["demotions_dirty"]


def test_batched_demote_bit_identical_metadata_only():
    base = _demote_cfg(store_payload=False)
    pool, ser, bat = _demote_pair(base)
    _check_pair(pool, ser, bat, what="metadata-only pools")


def test_batched_demote_end_to_end_steps():
    """Dispatch inside a jitted access loop: watermark top-up + read each
    step, serial vs batched configs end on bit-identical state."""
    base = _demote_cfg(store_payload=True)

    def run(cfg):
        @jax.jit
        def step(pool, ospn, blk):
            pool = OPS.demote_if_needed(pool, cfg, POL, max_demotes=3,
                                        watermark=8)
            pool, _ = OPS.read_block_op(pool, cfg, POL, ospn, blk)
            return pool
        pool = _victim_ready(_burst_pool(cfg, 36), cfg)
        for r in range(8):
            pool = step(pool, jnp.asarray(r % 36), jnp.asarray(r % 4))
        return pool

    ser = run(dataclasses.replace(base, fused_demote="off",
                                  compress_impl="jnp"))
    bat = run(dataclasses.replace(base, fused_demote="on",
                                  compress_impl="jnp"))
    _assert_trees_equal(ser, bat, "end-to-end pools")
    assert E.counters_dict(ser) == E.counters_dict(bat)


@pytest.mark.slow
def test_batched_demote_kernel_impl_bit_identical():
    """The full stack: batched demotion routed through the fused Pallas
    encode kernel (interpret mode off-TPU) vs the serial jnp reference."""
    base = PoolConfig(n_pages=32, n_cchunks=256, n_pchunks=16, mcache_sets=4,
                      mcache_ways=4, demote_watermark=4, store_payload=True)
    pool, ser, ker = _demote_pair(base, n_writes=24, bat_impl="kernel")
    _check_pair(pool, ser, ker, what="kernel-impl pools")


# -- measurement-calibrated device model -------------------------------------

def test_calibrated_device_from_bench_file(tmp_path):
    p = tmp_path / "BENCH_kernels.json"
    p.write_text(json.dumps({"calibration": {
        "compress_gbps": 4.0, "decompress_gbps": 64.0,
        "block_bytes": 1024}}))
    cal = TM.calibrated_device(path=p)
    base = TM.DeviceConfig()
    # cycles = clock * block_bytes / measured B/s
    assert cal.comp_cycles == round(base.clock * 1024 / 4e9)
    assert cal.decomp_cycles == round(base.clock * 1024 / 64e9)
    assert cal != base
    # everything but the engine constants is untouched
    assert dataclasses.replace(cal, comp_cycles=base.comp_cycles,
                               decomp_cycles=base.decomp_cycles) == base


def test_calibrated_device_fallback_paths(tmp_path):
    base = TM.DeviceConfig()
    assert TM.calibrated_device(path=tmp_path / "missing.json") == base
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert TM.calibrated_device(path=bad) == base
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert TM.calibrated_device(path=empty) == base
    # custom base is respected
    slow = TM.DEVICE_PROFILES["slow_engine"]
    assert TM.calibrated_device(path=tmp_path / "missing.json",
                                base=slow) == slow


def test_calibrated_device_committed_artifact():
    """The committed BENCH_kernels.json must actually move the engine
    constants away from the paper fallback (acceptance criterion)."""
    if not TM._BENCH_KERNELS.exists():
        pytest.skip("no committed BENCH_kernels.json")
    cal = TM.calibrated_device()
    base = TM.DeviceConfig()
    assert (cal.comp_cycles, cal.decomp_cycles) != \
        (base.comp_cycles, base.decomp_cycles)
    data = json.loads(TM._BENCH_KERNELS.read_text())
    assert data["calibration"]["compress_gbps"] > 0
    assert data["fused_vs_unfused"]["fused_ge_unfused"] is True
