#!/usr/bin/env python3
"""Chip smoke test: drive the three forms of IBEX's main path once on a TPU.

  python chip_smoke.py              # one chip: replay, fabric, Layer-A pool,
                                    # compressed-KV server
  python chip_smoke.py --chips 4    # four chips: only the sharded fabric at
                                    # D=4 against the vmap synchronous driver

Phases (one process, so one holder of the chip):

  1. device   — a TPU is required; the TPU-only switches must have picked
                the compiled paths (Pallas kernels, batched demotion).
  2. replay   — the paper's simulator (``simx``): ``ibex`` on the Table 2
                ``mcf`` workload, payload-less as simx runs it, over 2^17
                tracked 4 KiB pages (cut from a 4 GiB region of 2^20 to
                fit the run's time; the cut is printed), first-touch
                population then 10^5 measured accesses. At the CI smoke size
                the same replay also runs on the host CPU device, and every
                pool leaf (counters included) must match exactly.
  3. fabric   — the vmap pipelined driver, 4 expanders, spill migration,
                0.8 placement skew; the host-sync budgets are asserted.
  4. pool     — a Layer-A pool that stores payload bytes, through
                demotions and promotions: the fused encode/decode kernels
                run on the hot path and must be byte-identical to the jnp
                codec (the same pool run with ``compress_impl="jnp"``);
                then every qpack kernel against the jnp codec over 4096
                blocks of every rate.
  5. serve    — ``serve.Engine`` with ``deepseek_7b`` at its published
                widths (depth cut to fit one chip), 4-bit compressed KV,
                more requests than lanes so preemption and resume run.

Times printed are host-clock wall times (compile included, and compile
time on its own line); they are not device metrics. The last line of
stdout is one JSON object naming the device; a failed check raises, and
the script exits non-zero without printing it. With no TPU present it
exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# deepseek_7b keeps its widths; 8 of its 30 layers fit one 16 GB v5e chip
# with float32 weights. Described-v5e compile of the engine's decode step
# at this depth, 4 lanes, max_len 1024: arguments 10.11 GB (weights 9.83 GB,
# KV cache 0.28 GB) + outputs 0.28 GB + temporaries 3.24 GB = 13.6 GB; at
# 10 layers it is 16.2 GB, which does not fit.
SERVE_DEPTH = 8

# the replay's expander region: 2^20 tracked 4 KiB pages (4 GiB) is the
# size asked of it. First-touch population replays one write per tracked
# page through the serial slow-access drain, and on a TPU v5e a slow
# access costs more as the pool grows (about 2.2 ms each at 2^17 pages).
# At 2^17 the replay phase took 418 s there, population 312 s of it, and
# the four phases about 600 s of the run's 1200; 2^18 would put
# population alone near 600 s. So 2^17 is the largest size that fits.
FULL_REPLAY_PAGES = 1 << 20
REPLAY_PAGES = 1 << 17
REPLAY_ACCESSES = 100_000
# long enough that the 0.8 skew starves expander 0 and spill migration fires
FABRIC_ACCESSES = 2048


_COMPILE_TOTAL = [0.0]


def compile_seconds() -> float:
    """Seconds spent so far lowering to HLO and compiling it (tracing is
    left out: nested jits report their traces inside their parent's)."""
    return _COMPILE_TOTAL[0]


def _listen_for_compiles() -> None:
    import jax
    from jax._src import dispatch
    events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
              dispatch.BACKEND_COMPILE_EVENT}

    def listen(event, duration, **_):
        if event in events:
            _COMPILE_TOTAL[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)


class Phase:
    """Prints a phase's host wall time and the compile time inside it."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        print(f"== phase {self.name}", flush=True)
        self.t0, self.c0 = time.perf_counter(), compile_seconds()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            comp = compile_seconds() - self.c0
            print(f"phase {self.name}: wall {wall:.3f} s (host clock, "
                  f"compile included), compile {comp:.3f} s", flush=True)


def _gib(nbytes) -> str:
    return f"{nbytes / 2**30:.4f} GiB"


def _tree_bytes(tree) -> int:
    import jax
    return sum(int(a.size) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _trees_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(jax.devices()) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but {len(jax.devices())} "
                 f"TPU device(s) visible")
    from repro.common.types import PoolConfig
    from repro.core import compressor
    from repro.core.engine import ops
    from repro.kernels import qpack
    print(f"device: {dev.device_kind}, {len(jax.devices())} device(s), "
          f"jax {jax.__version__}", flush=True)
    assert compressor.resolve_impl(PoolConfig()) == "kernel"
    assert qpack.resolve_interpret(None) is False
    assert ops._use_batched_demote(PoolConfig())
    return dev


def _replay(pages: int, accesses: int, seed: int):
    """``ibex`` on ``mcf`` exactly as simx.engine.run_workload sizes it:
    a quarter of the pages promoted, first-touch population, then the
    trace. Returns (pool, pool config, {step: (wall s, compile s)})."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import batch as B
    from repro.core.engine import state as S
    from repro.core.engine.policy import POLICIES
    from repro.simx.engine import first_touch_populate, pool_cfg_for
    from repro.simx.trace import WORKLOADS, make_rates_table, make_trace
    spec, policy = WORKLOADS["mcf"], POLICIES["ibex"]
    prom = pages // 4
    cfg = pool_cfg_for(policy, n_pages=pages, n_pchunks=prom,
                       n_cchunks=2 * pages * 8)
    n_used = min(max(int(prom * spec.footprint_pages), 32), pages)
    rates = make_rates_table(spec, pages, seed=seed)
    ospn, wr, blk = make_trace(spec, n_accesses=accesses, n_pages=n_used,
                               seed=seed)
    pool = S.make_pool(cfg, seed=seed, rates_table=jnp.asarray(rates))
    times = {}

    def timed(name, fn, *a, **kw):
        t0, c0 = time.perf_counter(), compile_seconds()
        out = jax.block_until_ready(fn(*a, **kw))
        times[name] = (time.perf_counter() - t0, compile_seconds() - c0)
        return out

    pool = timed("populate", first_touch_populate, pool, cfg, policy,
                 n_used=n_used, seed=seed)
    pool = timed("replay", B.replay_trace, pool, cfg, policy, ospn, wr, blk)
    return pool, cfg, times


def phase_replay(args):
    import jax
    from repro.core.engine import state as S
    print(f"cut: replay pages {FULL_REPLAY_PAGES} -> {REPLAY_PAGES} "
          f"({_gib(FULL_REPLAY_PAGES * 4096)} -> {_gib(REPLAY_PAGES * 4096)}"
          f" expander region): first-touch population is one slow access "
          f"per page through the serial drain, about 2.2 ms each on a v5e "
          f"at 2^17 pages and more in a larger pool, so 2^17 is the largest "
          f"size whose phases fit the run's 20 minutes")
    pool, cfg, t = _replay(REPLAY_PAGES, REPLAY_ACCESSES, args.seed)
    c = S.counters_dict(pool)
    print(f"replay: {cfg.n_pages} pages ({_gib(cfg.n_pages * 4096)} "
          f"expander region), pool state on device {_gib(_tree_bytes(pool))}")
    for name, n in (("populate", cfg.n_pages),
                    ("replay", REPLAY_ACCESSES)):
        wall, comp = t[name]
        print(f"replay: {name} {n} accesses: wall {wall:.3f} s, of which "
              f"compile {comp:.3f} s -> {n / max(wall - comp, 1e-9):.1f} "
              f"accesses/s of wall time outside compile")
    print(f"replay counters: {c}")
    assert c["host_reads"] + c["host_writes"] == REPLAY_ACCESSES, c
    assert c["promotions"] > 0 and \
        c["demotions_clean"] + c["demotions_dirty"] > 0, c

    # the chip must reproduce the simulator's integer results exactly
    cpu = jax.devices("cpu")[0]
    small = dict(pages=128, accesses=2048, seed=args.seed)
    on_tpu, _, _ = _replay(**small)
    with jax.default_device(cpu):
        on_cpu, _, _ = _replay(**small)
    assert on_cpu.counters.devices() == {cpu}
    assert _trees_equal(on_tpu, on_cpu), \
        (S.counters_dict(on_tpu), S.counters_dict(on_cpu))
    print(f"replay parity: {small['pages']} pages x {small['accesses']} "
          f"accesses, TPU pool == CPU pool on every leaf (counters "
          f"{S.counters_dict(on_tpu)})")


def _fabric_setup(n_exp: int, pages: int, accesses: int, seed: int,
                  window: int):
    """The weighted-skew spill fabric of benchmarks/fabric_sharded.py."""
    import jax.numpy as jnp
    from repro.common.types import replace
    from repro.core.engine.policy import POLICIES
    from repro.fabric import Fabric, WeightedInterleave
    from repro.simx.engine import pool_cfg_for
    from repro.simx.trace import WORKLOADS, make_rates_table, make_trace
    cfg = replace(pool_cfg_for(POLICIES["ibex"], n_pages=pages,
                               n_pchunks=32, n_cchunks=2 * pages * 4),
                  n_cchunks=256)   # small enough that the skew starves e0
    spec = WORKLOADS["mcf"]
    rates = make_rates_table(spec, pages, seed=seed)
    trace = make_trace(spec, n_accesses=accesses, n_pages=pages, seed=seed)
    rest = (1.0 - 0.8) / (n_exp - 1)

    def make(**kw):
        return Fabric(cfg, POLICIES["ibex"],
                      WeightedInterleave(n_exp, pages,
                                         [0.8] + [rest] * (n_exp - 1)),
                      seed=seed, rates_table=jnp.asarray(rates), window=window,
                      migration="spill", spill_interval=512, spill_k=8,
                      spill_low=112, **kw)
    return make, trace


def phase_fabric(args):
    make, trace = _fabric_setup(4, 256, FABRIC_ACCESSES, args.seed, 16)
    t0 = time.perf_counter()
    fab = make().replay(*trace)
    dt = time.perf_counter() - t0
    ss = fab.sync_stats()
    assert ss["segment_syncs"] == ss["segments"], ss
    assert ss["epoch_syncs"] == ss["epochs"], ss
    c = fab.counters()
    assert c["host_reads"] + c["host_writes"] == FABRIC_ACCESSES, c
    print(f"fabric: 4 expanders, skew 0.8, {FABRIC_ACCESSES} accesses "
          f"in {dt:.3f} s wall (compile included); syncs {ss} (one per "
          f"segment + one per epoch, asserted); migration "
          f"{fab.spill_stats()}")


def phase_pool(args):
    """Layer-A pool with payload: the kernel codec against the jnp codec,
    same batched demotion, byte for byte."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.common.types import PoolConfig, replace
    from repro.core import engine as E
    from repro.core.compressor import resolve_impl
    pol = E.DEFAULT_POLICY
    # benchmarks/system_bench.py's Layer-A shape
    cfg_k = PoolConfig(n_pages=64, n_cchunks=512, n_pchunks=32,
                       mcache_sets=4, mcache_ways=4, demote_watermark=4,
                       store_payload=True)
    cfg_j = replace(cfg_k, compress_impl="jnp", fused_demote="on")
    assert resolve_impl(cfg_k) == "kernel" and resolve_impl(cfg_j) == "jnp"
    key = jax.random.PRNGKey(args.seed)
    n_write, n_pages = 96, 48
    pages = []
    for i in range(n_write):       # a mix that lands on every rate code
        k = jax.random.fold_in(key, i)
        kind = i % 4
        if kind == 0:
            x = jnp.zeros((cfg_k.vals_per_page,))
        elif kind == 1:
            x = jax.random.randint(k, (cfg_k.vals_per_page,), -7, 8) * 0.5
        elif kind == 2:
            x = jax.random.normal(k, (cfg_k.vals_per_page,)) * 0.1
        else:
            x = jax.random.normal(k, (cfg_k.vals_per_page,)) ** 3
        pages.append(x.astype(jnp.bfloat16))

    def run(cfg):
        pool = E.make_pool(cfg, seed=args.seed)
        for i, x in enumerate(pages):
            pool = E.host_write_page(pool, cfg, pol, jnp.asarray(i % n_pages),
                                     x)
        outs = []
        for i in range(n_pages):
            pool, v = E.host_read_block(pool, cfg, pol, jnp.asarray(i),
                                        jnp.asarray(i % cfg.blocks_per_page))
            outs.append(v)
        return pool, jnp.stack(outs)

    pool_k, out_k = run(cfg_k)
    pool_j, out_j = run(cfg_j)
    c = E.counters_dict(pool_k)
    assert c["demotions_dirty"] > 0 and c["promotions"] > 0, c
    assert _trees_equal(pool_k, pool_j), \
        (E.counters_dict(pool_k), E.counters_dict(pool_j))
    assert np.array_equal(np.asarray(out_k, np.float32),
                          np.asarray(out_j, np.float32))
    print(f"pool: {n_write} page writes + {n_pages} block reads, "
          f"demotions_dirty={c['demotions_dirty']} "
          f"promotions={c['promotions']}; kernel pool == jnp pool on every "
          f"leaf (payload bytes included), reads identical")
    _check_codec(args.seed)


def _check_codec(seed: int):
    """Every qpack kernel against the jnp codec on this device, over 4096
    blocks: the pool's 512-value bf16 blocks with rows cycling zero, exact
    4-bit, exact 8-bit, Gaussian and heavy-tailed values (every rate code
    under lossless selection), and the server's head_dim-128 f32 blocks
    for the fixed-rate kernels. Every byte, rate, scale and decoded value
    must match. A 512-value block with more than one kernel output is
    where 4-bit packing from lane-sliced planes once went wrong on a
    v5e."""
    import jax
    import jax.numpy as jnp
    from repro.common.types import PoolConfig
    from repro.core import compressor as C
    from repro.kernels import qpack as qp
    n = 4096
    g = jax.random.normal(jax.random.PRNGKey(seed), (n, 512))
    kind = (jnp.arange(n) % 5)[:, None]
    x = jnp.select(
        [kind == 0, kind == 1, kind == 2, kind == 3],
        [jnp.zeros_like(g),
         jnp.clip(jnp.round(g * 3), -7, 7).at[:, 0].set(7),
         jnp.clip(jnp.round(g * 40), -120, 120).at[:, 0].set(127),
         g * 0.7], g ** 3).astype(jnp.bfloat16)
    rates_seen = set()
    for lossless in (False, True):
        rates = C.select_rate(x, PoolConfig(lossless=lossless))
        dense = jax.vmap(C._encode_block_dense)(x, rates)
        got = qp.qpack_fused_encode_2d(x, lossless=lossless)
        assert _trees_equal(got[:2], (dense, rates)), lossless
        back = qp.qpack_fused_decode_2d(dense, rates)
        want = jax.vmap(lambda d, r: C._decode_block_dense(d, r, 512))(
            dense, rates)
        assert _trees_equal(back, want), lossless
        rates_seen |= set(rates.tolist())
    assert rates_seen == {0, 1, 2, 3}, rates_seen
    kv = jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                           (n, 128)) * 2
    for blocks in (x, kv):
        v = blocks.shape[1]
        for bits in (4, 8):
            codes, scales = qp.qpack_encode_2d(blocks, bits=bits)
            want = C.quantize_blocks(blocks, bits, v)      # scales [n, 1]
            assert _trees_equal((codes, scales[:, None]), want), (v, bits)
            assert _trees_equal(
                qp.qpack_decode_2d(codes, scales, bits=bits),
                C.dequantize_blocks(*want, bits, v)), (v, bits)
    print(f"codec: {n} blocks of 512 bf16 values (rates "
          f"{sorted(rates_seen)}, lossy and lossless) and of 128 f32 values: "
          f"fused and fixed-rate (4- and 8-bit) qpack kernels == jnp codec "
          f"on every byte, rate, scale and decoded value")


def phase_serve(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.common.types import ServeConfig, replace
    from repro.configs import get_config
    from repro.core.compressor import quantize_blocks, quantize_blocks_fast
    from repro.models import decode as D
    from repro.models import transformer as T
    from repro.serve import Engine
    from repro.serve import engine as SE
    full = get_config("deepseek_7b")
    cfg = replace(full, num_layers=SERVE_DEPTH)
    print(f"cut: deepseek_7b num_layers {full.num_layers} -> {cfg.num_layers} "
          f"(widths kept: d_model {cfg.d_model}, {cfg.num_heads} MHA heads, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; float32 weights)")
    lanes, max_len, n_req, new_tokens = 4, 1024, 8, 32
    scfg = ServeConfig(max_running=lanes)
    assert scfg.kv_rate_bits == 4
    params = jax.jit(lambda k: T.init_params(k, cfg)[0])(
        jax.random.PRNGKey(args.seed))
    eng = Engine(cfg, scfg, params, max_len=max_len)
    print(f"serve: weights {_gib(_tree_bytes(params))}, KV cache "
          f"{_gib(D.cache_bytes(eng.cache))} ({lanes} lanes x {max_len} "
          f"tokens, {scfg.kv_rate_bits}-bit codes + {scfg.hot_window}-token "
          f"bf16 ring)")
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(128, 513, size=n_req)
    rids = [eng.submit(rng.integers(1, cfg.vocab_size, int(n)).tolist(),
                       new_tokens) for n in lens]
    t0 = time.perf_counter()
    eng.run_until_done()
    dt = time.perf_counter() - t0
    c = eng.counters
    print(f"serve: {n_req} requests (prompts {sorted(lens.tolist())}, "
          f"{new_tokens} new tokens) over {lanes} lanes in {dt:.3f} s wall "
          f"(compile included); counters {c}")
    for rid in rids:
        req = eng.requests[rid]
        assert req.state == "done" and len(req.generated) == new_tokens, \
            (rid, req.state, len(req.generated))
        assert all(0 <= t < cfg.vocab_size for t in req.generated)
    assert c["promotions"] > 0 and c["demotions"] > 0, c
    assert c["step_syncs"] == c["steps"], c

    # the compiled qpack encode on one demoted lane's KV against the oracle
    lane = 0
    lane_cache = SE._lane_slice(eng.cache, lane)
    pos = eng.state["pos"][lane]
    for key in ("k_hot", "v_hot"):
        hot = lane_cache[key].astype(jnp.float32)
        assert bool(jnp.any(hot != 0)), key
        head_dim = hot.shape[-1]
        got = quantize_blocks_fast(hot, scfg.kv_rate_bits, head_dim,
                                   impl="kernel")
        want = quantize_blocks(hot, scfg.kv_rate_bits, head_dim)
        assert _trees_equal(got, want), key
    demoted = eng._demote_fn(lane_cache, pos)
    oracle = jax.jit(functools.partial(
        SE._demote_lane_impl, scfg=replace(scfg, quantize_impl="jnp")))(
            lane_cache, pos)
    assert _trees_equal(demoted, oracle)
    assert not np.array_equal(np.asarray(demoted["k_codes"]),
                              np.asarray(lane_cache["k_codes"]))
    stats = jax.devices()[0].memory_stats() or {}
    print(f"serve: lane {lane} demotion through the compiled qpack encode "
          f"== jnp quantize_blocks (codes and scales, K and V); device "
          f"peak {_gib(stats.get('peak_bytes_in_use', 0))} of "
          f"{_gib(stats.get('bytes_limit', 0))}")


def phase_sharded(args):
    """D=4 sharded fabric against the vmap synchronous driver. The
    sharded driver unrolls the slow drain over the window, so its compile
    grows with the window (described-v5e compile of the boundary step:
    81 s at window 4, 143 s at 8); 4 keeps the four-chip call short."""
    import jax
    make, trace = _fabric_setup(4, 256, FABRIC_ACCESSES, args.seed, 4)
    ref = make(sync_migration=True).replay(*trace)
    assert ref.spill_stats()["events"] > 0, ref.spill_stats()
    print(f"vmap synchronous reference: {ref.sync_stats()}, migration "
          f"{ref.spill_stats()}")

    t0 = time.perf_counter()
    fab = make(shard_devices=4).replay(*trace)
    jax.block_until_ready(fab.pools)
    dt = time.perf_counter() - t0
    ss = fab.sync_stats()
    assert ss["segment_syncs"] == 0 and ss["epoch_syncs"] == 0, ss
    assert ss["boundary_syncs"] == ss["boundaries"], ss
    assert fab.state_identical(ref), \
        "sharded driver drifted from the vmap reference"
    print(f"sharded: D=4 on {jax.device_count()} chips: {dt:.3f} s wall "
          f"(compile included); syncs {ss}; bit-identical to the vmap "
          f"synchronous driver")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded fabric on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not __debug__:
        sys.exit("chip_smoke: its checks are asserts; run it without -O")

    import jax
    from repro.common.compile_cache import enable_compile_cache
    _listen_for_compiles()
    dev = phase_device(args.chips)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        with Phase("sharded"):
            phase_sharded(args)
    else:
        for name, phase in (("replay", phase_replay),
                            ("fabric", phase_fabric), ("pool", phase_pool),
                            ("serve", phase_serve)):
            with Phase(name):
                phase(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
