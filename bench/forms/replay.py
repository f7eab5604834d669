"""Replay form: the simulator's trace replay through one payload-less IBEX
expander (``repro.core.engine.batch.replay_trace``).

Set-up builds the pool from the configuration file, populates it by first
touch (one write per tracked page slot, then the counters are zeroed, as the
simulator's users do), generates the mix's trace and warms the one chunk
shape the window replays. The work is drawn once from the mix's
``work_seed``; ``--seed`` renames the pages (``gen.rename_pages``) and seeds
the pool, so that every seed replays the same work under other page names
(with a wholly new trace per seed, the rate spread by 2% on the chip).

The window replays the trace in chunks of ``chunk`` accesses, wrapping
around at its end, with up to ``dispatch_ahead`` chunks dispatched beyond
the one it waits for, until ``--seconds`` have passed; then it dispatches
nothing more and waits for every chunk sent. The rate counts every access
of every chunk over all of that time.

``correct`` compares the program's pool, every leaf, after the first chunks
of the window against the plain reference (``bench/reference/pool.py``)
replaying the same population and chunks: an exact comparison.
"""
from __future__ import annotations

import collections
import time

import numpy as np

import gen
from reference import pool as ref


class Form:
    LABELS = {"replay.chunk": "replay.chunk",
              "batch.py:replay_trace": "replay.replay_trace"}

    def __init__(self, cell, devices):
        from repro.common.types import PoolConfig
        from repro.core.engine.policy import POLICIES
        self.t_created = time.perf_counter()
        self.cell = cell
        self.pool_fields = cell.config["pool"]
        self.cfg = PoolConfig(**self.pool_fields)
        self.policy = POLICIES[cell.config["policy"]]
        self.window_len = int(cell.config["window"])
        self.replay_trace = None          # bound in setup; a test may wrap it

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core.engine import batch as B
        from repro.core.engine import state as S
        if self.replay_trace is None:
            self.replay_trace = B.replay_trace
        mix, cfg = self.cell.mix, self.cfg
        # the work (rate codes, trace, first-touch order) is drawn once from
        # the mix's work_seed; --seed names the pages anew and seeds the
        # pool, so that every seed replays the same work
        w_rates, w_trace, w_order = gen.sub_seeds(int(mix["work_seed"]), 3)
        s_names, s_pool = gen.sub_seeds(self.cell.seed, 2)
        self.pool_seed = s_pool
        rates = gen.table2_rates(mix, cfg.n_pages, w_rates)
        n_used = gen.footprint_pages(mix, cfg.n_pchunks, cfg.n_pages)
        order = gen.first_touch_order(n_used, cfg.n_pages, w_order)
        self.chunk = int(mix["chunk"])
        ospn, wr, blk = gen.table2_trace(mix, int(mix["trace_accesses"]),
                                         n_used, w_trace)
        self.rates, self.order, ospn = gen.rename_pages(s_names, rates,
                                                        order, ospn)
        self.trace_host = (ospn, wr, blk)
        n_chunks = ospn.shape[0] // self.chunk
        split = jax.jit(lambda a: tuple(a.reshape(n_chunks, -1)))
        cols = [split(jnp.asarray(a)) for a in (ospn, wr, blk)]
        self.chunks = list(zip(*cols))

        pool = S.make_pool(cfg, seed=s_pool, rates_table=jnp.asarray(self.rates))
        pool = self.replay_trace(
            pool, cfg, self.policy, self.order,
            np.ones((cfg.n_pages,), bool), np.zeros((cfg.n_pages,), np.int32),
            window=self.window_len)
        self.pool = pool._replace(counters=jnp.zeros_like(pool.counters))
        # warm the window's one chunk shape on a throwaway result
        jax.block_until_ready(self.replay_trace(
            self.pool, cfg, self.policy, *self.chunks[0],
            window=self.window_len))
        self.n_used = n_used

    # -- the measured window --------------------------------------------------

    def window(self, seconds, trace_dir):
        import jax
        from repro.core.engine import state as S
        from run import Window
        mix = self.cell.mix
        if trace_dir is not None:
            seconds = min(seconds, float(mix["trace_seconds"]))
        keep = int(mix["check_chunks"])
        snapshots = []
        pool = self.pool
        before = np.asarray(pool.counters)
        n_chunks = len(self.chunks)
        done = 0

        def record(p):
            if done <= keep:
                snapshots.append(p)

        from run import traced
        # chunks dispatched beyond the one waited for, so that the device
        # never waits on the host between chunks: the mix's dispatch_ahead
        ahead = int(mix.get("dispatch_ahead", 1))
        inflight = collections.deque()
        with traced(trace_dir):
            t0 = time.perf_counter()
            dispatched = 0
            while True:
                with jax.profiler.TraceAnnotation("replay.chunk"):
                    pool = self.replay_trace(
                        pool, self.cfg, self.policy,
                        *self.chunks[dispatched % n_chunks],
                        window=self.window_len)
                dispatched += 1
                inflight.append(pool)
                if len(inflight) > ahead:
                    p = inflight.popleft()
                    jax.block_until_ready(p)
                    done += 1
                    record(p)
                    if time.perf_counter() - t0 >= seconds:
                        break
            # time is up: send nothing more, wait for all that was sent
            while inflight:
                p = inflight.popleft()
                jax.block_until_ready(p)
                done += 1
                record(p)
            t1 = time.perf_counter()
        self.final_pool = pool
        self.snapshots = snapshots
        delta = np.asarray(pool.counters).astype(np.int64) - before
        names = S.COUNTER_NAMES
        c = dict(zip(names, (int(x) for x in delta)))
        accesses = done * self.chunk
        self.host_count_diff = abs(c["host_reads"] + c["host_writes"]
                                   - accesses)
        facts = {"accesses": accesses, "chunks": done,
                 "window_s": t1 - t0,
                 "moves": c["promotions"] + c["demotions_clean"]
                 + c["demotions_dirty"],
                 "promotions": c["promotions"],
                 "demotions_clean": c["demotions_clean"],
                 "demotions_dirty": c["demotions_dirty"],
                 "n_pages": self.cfg.n_pages, "footprint_pages": self.n_used}
        return Window(e2e={"sim_accesses_per_s": accesses / (t1 - t0)},
                      attempted=accesses, failed=0, facts=facts)

    def release(self):
        """Bring the checked snapshot to the host and drop device state."""
        self.checked_chunks = len(self.snapshots)
        self.program = ref.program_leaves(self.snapshots[-1])
        self.snapshots = self.chunks = self.pool = self.final_pool = None

    # -- correctness ------------------------------------------------------------

    def reference(self, control=None):
        """The reference pool after the checked chunks; ``control`` names
        the guarantee the control breaks (``shadow``, ``mcache``)."""
        brk = {} if control is None else {f"break_{control}": True}
        r = ref.RefPool(self.pool_fields, self.rates, self.pool_seed, **brk)
        n = self.cfg.n_pages
        r.replay(self.order, np.ones((n,), bool), np.zeros((n,), np.int32),
                 self.window_len)
        r.zero_counters()
        ospn, wr, blk = self.trace_host
        n_chunks = ospn.shape[0] // self.chunk
        for i in range(self.checked_chunks):
            s = (i % n_chunks) * self.chunk
            e = s + self.chunk
            r.replay(ospn[s:e], wr[s:e], blk[s:e], self.window_len)
        return r.leaves()

    def check(self, control=False):
        want = self.reference()
        got = self.reference(self.cell.mix["control"]) if control \
            else self.program
        return {"pool_diff": (ref.count_differences(want, got), 0),
                "host_count_diff": (self.host_count_diff, 0)}
