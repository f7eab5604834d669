"""Tests for the jit-hygiene static analyzer (DESIGN.md §15).

Layout mirrors the satellite spec: per-rule positive / negative /
suppressed fixtures, a baseline round-trip, a self-check that the
committed baseline matches a fresh run over src/ (no stale entries), and
the two acceptance demos — a synthetic ``int(traced)`` injected into a
real jitted body fails the lint, and stripping any one ``@sync_contract``
annotation fails the lint.

Everything except the runtime-contract cross-checks is stdlib-only (the
analyzer must run with no jax installed).
"""
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import baseline as baseline_mod
from repro.analysis.core import ModuleInfo
from repro.analysis.lint import lint_file, run_lint
from repro.common.contracts import (SyncContract, get_sync_contract,
                                    sync_contract, verify_sync_counters)

REPO = Path(__file__).resolve().parent.parent
BASELINE = REPO / "src" / "repro" / "analysis" / "baseline.json"


def _lint_src(code: str, name: str = "snippet.py"):
    return lint_file(name, relpath=name, src=textwrap.dedent(code))


def _rules(findings, *, suppressed=False):
    return [f.rule for f in findings if f.suppressed == suppressed]


# ---------------------------------------------------------------------------
# R1 — hidden host sync
# ---------------------------------------------------------------------------

def test_r1_positive_casts_and_branches():
    fs = _lint_src("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                y = int(x)
            while x < 9:
                x = x + 1
            return x.item()
    """)
    msgs = [f.message for f in fs]
    assert _rules(fs).count("R1") == 4, fs
    assert any("`if`" in m for m in msgs)
    assert any("`while`" in m for m in msgs)
    assert any("int()" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


def test_r1_numpy_print_device_get():
    fs = _lint_src("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            a = np.sum(x)
            print(x)
            b = jax.device_get(x)
            return a
    """)
    assert _rules(fs).count("R1") == 3, fs


def test_r1_negative_static_metadata_structural():
    fs = _lint_src("""
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, cfg, valid=None):
            if cfg.mode == "fast":          # static param
                x = x + 1
            if x.shape[0] > 4:              # trace-time metadata
                x = x * 2
            if valid is None:               # structural identity
                valid = x
            out = {"x": x}
            if "x" in out:                  # structural membership
                x = out["x"]
            n = np.arange(cfg.n)            # numpy on static only
            return x
    """)
    assert fs == [], [f.render() for f in fs]


def test_r1_suppressed_counts_but_passes():
    fs = _lint_src("""
        import jax

        @jax.jit
        def f(x):
            n = int(x)  # lint: host-ok(debug counter, removed in prod)
            return x
    """)
    assert _rules(fs, suppressed=True) == ["R1"]
    assert _rules(fs) == []
    assert fs[0].suppress_reason == "debug counter, removed in prod"


def test_r1_combinator_bodies_and_call_propagation():
    fs = _lint_src("""
        import jax

        def helper(v, cfg):
            if cfg.fast:            # static at the only call site
                v = v + 1
            return int(v)           # tainted via propagation

        def outer(xs, cfg):
            def body(c, x):
                return helper(c, cfg), x
            return jax.lax.scan(body, 0, xs)
    """)
    assert _rules(fs) == ["R1"], [f.render() for f in fs]
    assert "int()" in fs[0].message


def test_r1_jit_call_site_partial_kwargs_static():
    fs = _lint_src("""
        import functools
        import jax

        def impl(state, cfg=None):
            if cfg.windows > 1:     # partial-bound -> static
                state = state + 1
            return state

        step = jax.jit(functools.partial(impl, cfg=object()))
    """)
    assert fs == [], [f.render() for f in fs]


# ---------------------------------------------------------------------------
# R2 — recompile hazards
# ---------------------------------------------------------------------------

def test_r2_mutable_default_and_bad_static_names():
    fs = _lint_src("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("nope",))
        def f(x, real):
            return x

        @jax.jit
        def g(x, cache={}):
            return x

        @functools.partial(jax.jit, static_argnums=(5,))
        def h(x, y):
            return x
    """)
    assert sorted(_rules(fs)) == ["R2", "R2", "R2"], [f.render() for f in fs]


def test_r2_varying_static_kwarg_at_call():
    fs = _lint_src("""
        import jax

        def impl(x, mode):
            return x

        f = jax.jit(impl, static_argnames=("mode",))

        def call(x, i):
            return f(x, mode=f"bucket{i}")
    """)
    assert _rules(fs) == ["R2"], [f.render() for f in fs]
    assert "per-call-varying" in fs[0].message


def test_r2_negative_clean_jit():
    fs = _lint_src("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("cfg",))
        def f(x, cfg=None):
            return x
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# R3 — counter layout drift
# ---------------------------------------------------------------------------

def test_r3_literal_counter_index():
    fs = _lint_src("""
        def report(counters, tvec):
            a = counters[3]
            b = tvec[0]
            return a + b
    """)
    assert _rules(fs) == ["R3", "R3"]


def test_r3_negative_named_and_variable_indices():
    fs = _lint_src("""
        from repro.core.engine import state as S

        def report(counters, i):
            a = counters[S.C_DATA_RD]
            b = counters[i]
            c = counters[2:5]          # slices allowed
            flags = [0, 1][0]          # not a counter vector
            return a + b
    """)
    assert fs == [], [f.render() for f in fs]


def test_r3_suppressed():
    fs = _lint_src("""
        def report(ctrs):
            return ctrs[0]  # lint: host-ok(layout pinned by golden file)
    """)
    assert _rules(fs, suppressed=True) == ["R3"]
    assert _rules(fs) == []


# ---------------------------------------------------------------------------
# R4 — pallas hygiene
# ---------------------------------------------------------------------------

def test_r4_literal_interpret():
    fs = _lint_src("""
        from jax.experimental import pallas as pl

        def launch(x, kern):
            return pl.pallas_call(kern, grid=(4,), interpret=True)(x)
    """)
    assert _rules(fs) == ["R4"]
    assert "resolve_interpret" in fs[0].message


def test_r4_blockspec_arity_mismatches():
    fs = _lint_src("""
        from jax.experimental import pallas as pl

        def launch(x, kern, out_shape):
            return pl.pallas_call(
                kern, grid=(4, 2),
                in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i,)),
                out_shape=out_shape)(x)
    """)
    msgs = [f.message for f in fs]
    assert _rules(fs) == ["R4", "R4"], [f.render() for f in fs]
    assert any("grid has 2" in m for m in msgs)
    assert any("1 index(es) for a 2-axis" in m for m in msgs)


def test_r4_negative_resolved_interpret():
    fs = _lint_src("""
        from jax.experimental import pallas as pl

        def launch(x, kern, interpret, out_shape):
            return pl.pallas_call(
                kern, grid=(4,),
                in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
                out_shape=out_shape,
                interpret=interpret)(x)
    """)
    assert fs == [], [f.render() for f in fs]


# ---------------------------------------------------------------------------
# R5 — sync contracts
# ---------------------------------------------------------------------------

def test_r5_budget_and_loop_findings():
    fs = _lint_src("""
        import jax
        import numpy as np
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="step", fetches=1)
            def step(self):
                a = jax.device_get(self.state)
                b = jax.device_get(self.pools.counters)   # over budget
                for lane in self.lanes:
                    c = self.tok.item()                   # loop fetch
                return a, b, c
    """)
    msgs = [f.message for f in fs]
    assert _rules(fs).count("R5") == 2, [f.render() for f in fs]
    assert any("exceeds the declared budget" in m for m in msgs)
    assert any("inside a host loop" in m for m in msgs)


def test_r5_negative_single_fused_fetch():
    fs = _lint_src("""
        import jax
        import numpy as np
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="segment", fetches=1)
            def fetch_view(self, times, stats):
                stats, ctrs, t = jax.device_get(
                    (stats, self.pools.counters, times))
                ctrs = np.asarray(ctrs, np.int64)         # host already
                free = np.asarray(stats.free_units, np.int64)
                return ctrs, free, t
    """)
    assert fs == [], [f.render() for f in fs]


def test_r5_device_sourced_np_asarray_counts():
    fs = _lint_src("""
        import jax
        import numpy as np
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="epoch", fetches=1)
            def commit(self):
                moved = jax.device_get(self.moved)
                extra = np.asarray(self.pools.counters)   # 2nd fetch
                return moved, extra
    """)
    assert _rules(fs) == ["R5"], [f.render() for f in fs]
    assert "exceeds the declared budget" in fs[0].message


def test_r5_suppressed_site_excluded_from_budget():
    fs = _lint_src("""
        import jax
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="step", fetches=1)
            def step(self):
                a = jax.device_get(self.state)
                b = jax.device_get(self.dbg)  # lint: host-ok(debug-only path)
                return a, b
    """)
    assert _rules(fs) == [], [f.render() for f in fs]
    assert _rules(fs, suppressed=True) == ["R5"]


# ---------------------------------------------------------------------------
# R6 — obs telemetry piggyback
# ---------------------------------------------------------------------------

def test_r6_emission_inside_jit_region():
    fs = _lint_src("""
        import jax

        @jax.jit
        def kernel(pool, obs):
            obs.record_segment(0, pool.counters, None, None)
            return pool
    """)
    assert _rules(fs).count("R6") == 1, [f.render() for f in fs]
    assert "inside a jit region" in fs[0].message


def test_r6_emission_in_traced_combinator_body():
    fs = _lint_src("""
        import jax

        def scan_all(xs, obs):
            def body(carry, x):
                obs.record_step(carry, x, x, x, [])
                return carry, x
            return jax.lax.scan(body, 0, xs)
    """)
    assert "R6" in _rules(fs), [f.render() for f in fs]


def test_r6_device_value_handed_to_drain_in_contract():
    fs = _lint_src("""
        import jax
        import numpy as np
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="segment", fetches=1)
            def fetch_view(self, times):
                ctrs = jax.device_get(self.pools.counters)
                # the drain below is handed LIVE device state — the
                # Recorder's np.asarray would be a hidden second sync
                self.obs.record_segment(0, self.pools.counters,
                                        np.asarray(ctrs), None)
                return ctrs
    """)
    r6 = [f for f in fs if f.rule == "R6"]
    assert len(r6) == 1, [f.render() for f in fs]
    assert "hidden second sync" in r6[0].message


def test_r6_negative_host_drain_is_sanctioned():
    """The repo's actual drain shape: everything the Recorder is handed
    was bound from the single contracted fetch (or is host bookkeeping,
    like a string-keyed dict counter) — no findings."""
    fs = _lint_src("""
        import jax
        import numpy as np
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="step", fetches=1)
            def step(self, done, active):
                tok_h, done_h, ref_h, pos_h = self._fetch(
                    (self.state, done, self.ref, self.pos))
                if self.obs is not None:
                    self.obs.record_step(self.counters["steps"], tok_h,
                                         done_h, pos_h,
                                         [lane for lane, _ in active])
                return tok_h
    """)
    assert _rules(fs) == [], [f.render() for f in fs]


def test_r6_device_producer_call_as_drain_arg():
    fs = _lint_src("""
        import jax
        import jax.numpy as jnp
        from repro.common.contracts import sync_contract

        class Eng:
            @sync_contract(syncs_per="epoch", fetches=1)
            def commit(self):
                moved = jax.device_get(self.moved)
                self.obs.record_epoch(0, jnp.sum(self.pools.counters),
                                      kind="sync", overlapped=False,
                                      planned=0, moved=0, urgent=False,
                                      free_units=moved)
                return moved
    """)
    r6 = [f for f in fs if f.rule == "R6"]
    assert len(r6) >= 1, [f.render() for f in fs]


# ---------------------------------------------------------------------------
# Baseline ratchet
# ---------------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    code = """
        import jax

        @jax.jit
        def f(x):
            return int(x)
    """
    findings = _lint_src(code)
    assert _rules(findings) == ["R1"]
    bpath = tmp_path / "baseline.json"
    baseline_mod.save(bpath, findings, note="test")
    loaded = baseline_mod.load(bpath)
    new, old, stale = baseline_mod.diff(findings, loaded)
    assert new == [] and len(old) == 1 and stale == []
    # fingerprints are line-number-free: shifting the file leaves the
    # finding grandfathered
    shifted = _lint_src("\n\n# moved\n" + textwrap.dedent(code))
    new, old, stale = baseline_mod.diff(shifted, loaded)
    assert new == [] and len(old) == 1 and stale == []
    # a SECOND instance of the same mistake is new (multiset semantics)
    doubled = _lint_src(code + """
        @jax.jit
        def f(x):
            return int(x)
    """)
    new, old, stale = baseline_mod.diff(doubled, loaded)
    assert len(new) == 1 and len(old) == 1
    # fixing the finding leaves a stale entry the self-check reports
    new, old, stale = baseline_mod.diff([], loaded)
    assert new == [] and old == [] and len(stale) == 1


def test_committed_baseline_matches_fresh_run():
    """The committed baseline is exactly the debt a fresh run over src/
    reports: no new findings (lint passes) and no stale entries (the
    baseline never overstates the debt)."""
    report = run_lint([str(REPO / "src")], baseline_path=BASELINE)
    assert report["counts"]["parse_errors"] == 0
    assert report["new"] == [], json.dumps(report["new"], indent=2)
    assert report["stale_baseline"] == [], report["stale_baseline"]


# ---------------------------------------------------------------------------
# Acceptance demos: the lint fails when the contracts regress
# ---------------------------------------------------------------------------

def test_injected_int_traced_fails_lint():
    """Adding a synthetic ``int(traced)`` to a real jitted body in
    core/engine/batch.py produces a new R1 finding — the CI step
    (which diffs against the committed baseline) would fail."""
    path = REPO / "src" / "repro" / "core" / "engine" / "batch.py"
    src = path.read_text()
    marker = "def _window_step(pool"
    assert marker in src
    lines = src.splitlines()
    idx = next(i for i, l in enumerate(lines) if marker in l)
    while not lines[idx].rstrip().endswith(":"):  # signature may wrap
        idx += 1
    # first statement line of the body: inject a concretizing cast of a
    # parameter that is traced (pool) under the jitted callers
    indent = " " * 4
    lines.insert(idx + 1, f"{indent}_dbg = int(pool.counters[0] * 1)")
    mutated = "\n".join(lines)
    before = [f for f in lint_file(path, relpath="src/repro/core/engine/"
                                   "batch.py") if not f.suppressed]
    after = [f for f in lint_file(path, relpath="src/repro/core/engine/"
                                  "batch.py", src=mutated)
             if not f.suppressed]
    new_rules = sorted(_rules(after))
    for f in before:
        assert not f.rule == "R1", "hot path must be R1-clean"
    assert "R1" in new_rules, [f.render() for f in after]
    base = baseline_mod.load(BASELINE)
    new, _, _ = baseline_mod.diff(after, base)
    assert any(f.rule == "R1" for f in new)


@pytest.mark.parametrize("relsuffix, qualname", [
    ("src/repro/serve/engine.py", "Engine.step"),
    ("src/repro/fabric/replay.py", "Fabric._fetch_view"),
    ("src/repro/fabric/replay.py", "Fabric._commit_epoch"),
])
def test_stripping_any_sync_contract_fails_lint(relsuffix, qualname):
    """Deleting any one @sync_contract annotation is itself a new R5
    finding (REQUIRED_CONTRACTS), so the annotation cannot be removed to
    appease the fetch count."""
    path = REPO / relsuffix
    src = path.read_text()
    method = qualname.split(".")[-1]
    lines = src.splitlines()
    hits = [i for i, l in enumerate(lines)
            if l.strip().startswith("@sync_contract")
            and f"def {method}(" in "\n".join(lines[i + 1:i + 3])]
    assert len(hits) == 1, f"expected one annotation for {qualname}"
    del lines[hits[0]]
    stripped = "\n".join(lines)
    clean = [f for f in lint_file(path, relpath=relsuffix)
             if not f.suppressed]
    assert not any(f.rule == "R5" for f in clean)
    after = [f for f in lint_file(path, relpath=relsuffix, src=stripped)
             if not f.suppressed]
    missing = [f for f in after if f.rule == "R5"
               and "missing" in f.message and qualname in f.message]
    assert missing, [f.render() for f in after]
    base = baseline_mod.load(BASELINE)
    new, _, _ = baseline_mod.diff(after, base)
    assert any(f.rule == "R5" for f in new)


# ---------------------------------------------------------------------------
# Runtime half: @sync_contract attribute + verify_sync_counters
# ---------------------------------------------------------------------------

def test_contract_attribute_no_wrapper():
    calls = []

    @sync_contract(syncs_per="step", fetches=1)
    def f(x):
        calls.append(x)
        return x + 1

    assert f(1) == 2 and calls == [1]
    assert f.__name__ == "f"                      # no wrapper frame
    assert get_sync_contract(f) == SyncContract("step", 1)
    assert get_sync_contract(f).expected_syncs(7) == 7


def test_verify_sync_counters():
    @sync_contract(syncs_per="segment", fetches=1)
    def f():
        pass

    verify_sync_counters(f, n_events=5, n_syncs=5)
    with pytest.raises(AssertionError, match="measured 6 syncs"):
        verify_sync_counters(f, n_events=5, n_syncs=6)

    def bare():
        pass

    with pytest.raises(AssertionError, match="declares no @sync_contract"):
        verify_sync_counters(bare, n_events=1, n_syncs=1)


def test_hot_paths_declare_contracts():
    """The three load-bearing contracts are attached at runtime too (the
    runtime cross-checks resolve them via get_sync_contract)."""
    from repro.fabric.replay import Fabric
    from repro.serve.engine import Engine

    assert get_sync_contract(Engine.step) == SyncContract("step", 1)
    assert get_sync_contract(Fabric._fetch_view) == \
        SyncContract("segment", 1)
    assert get_sync_contract(Fabric._commit_epoch) == \
        SyncContract("epoch", 1)
