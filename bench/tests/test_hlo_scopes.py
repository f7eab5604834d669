"""The scope of each instruction read from HLO text, and device op time
charged to the program's scopes through the replay program this process
compiled."""
import jax
import pytest

import hlo_scopes as H
import trace_reduce as TR

SCOPES = ("ibex.topup", "ibex.drain")

HLO = """HloModule jit_f, entry_computation_layout={(s32[])->s32[]}

%fused_computation.1 (param_0: s32[]) -> s32[] {
  %param_0 = s32[] parameter(0)
  ROOT %add.9 = s32[] add(%param_0, %param_0), metadata={op_name="jit(f)/ibex.topup/add"}
}

%body.2 (arg.3: s32[]) -> s32[] {
  %arg.3 = s32[] parameter(0)
  %copy.4 = s32[] copy(%arg.3)
  ROOT %fusion.5 = s32[] fusion(%copy.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/ibex.drain/ibex.topup/add"}
}

%cond.6 (arg.7: s32[]) -> pred[] {
  %arg.7 = s32[] parameter(0)
  ROOT %lt.8 = pred[] compare(%arg.7, %arg.7), direction=LT
}

%branch.10 (p.11: s32[]) -> s32[] {
  ROOT %p.11 = s32[] parameter(0)
}

ENTRY %main.12 (x.13: s32[]) -> s32[] {
  %x.13 = s32[] parameter(0)
  %while.14 = s32[] while(%x.13), condition=%cond.6, body=%body.2, metadata={op_name="jit(f)/ibex.drain/while"}
  %copy.15 = s32[] copy(%while.14)
  ROOT %conditional.16 = s32[] conditional(%x.13, %copy.15, %copy.15), branch_computations={%branch.10, %branch.10}, metadata={op_name="jit(f)/ibex.topup/cond"}
}
"""


def test_scopes_own_innermost_and_inherited_by_hand():
    sc = H.instruction_scopes(HLO, SCOPES)
    assert sc["while.14"] == "ibex.drain"
    # a copy XLA put into the loop body takes the loop's scope
    assert sc["copy.4"] == "ibex.drain"
    assert sc["lt.8"] == "ibex.drain"
    # the innermost declared component of an op's own op_name wins
    assert sc["fusion.5"] == "ibex.topup"
    assert sc["p.11"] == "ibex.topup"          # a conditional's branch
    assert sc["copy.15"] is None               # entry, no op_name
    assert sc["x.13"] is None


def test_no_entry_no_scopes():
    assert H.instruction_scopes("", SCOPES) == {}
    assert all(v is None
               for v in H.instruction_scopes(HLO, ("other",)).values())


@pytest.fixture(scope="module")
def replay_program():
    """Compile and run the replay program at a small size; returns the
    instruction names of its one live executable."""
    import numpy as np
    from repro.common.types import PoolConfig
    from repro.core.engine import batch as B
    from repro.core.engine import state as S
    from repro.core.engine.policy import POLICIES
    cfg = PoolConfig(n_pages=64, n_pchunks=16, n_cchunks=1024,
                     mcache_sets=2)
    pool = S.make_pool(cfg, seed=0)
    rng = np.random.default_rng(0)
    n = 256
    out = B.replay_trace(pool, cfg, POLICIES["ibex"],
                         rng.integers(0, 64, n).astype(np.int32),
                         rng.random(n) < 0.2,
                         rng.integers(0, 4, n).astype(np.int32), window=32)
    jax.block_until_ready(out)
    names = {}
    for exe in jax.devices()[0].client.live_executables():
        for mod in exe.hlo_modules():
            if mod.name == "jit__replay_windows":
                names = H.instruction_scopes(mod.to_string(),
                                             H.declared_scopes())
    return names


def test_replay_program_carries_every_scope(replay_program):
    found = set(replay_program.values())
    assert set(H.declared_scopes()) <= found


def test_scope_seconds_joins_op_times_by_instruction_name(replay_program,
                                                          monkeypatch):
    by_scope = {}
    for name, sc in replay_program.items():
        by_scope.setdefault(sc or H.UNSCOPED, name)
    red = TR.Reduced(window_s=1.0, busy_s=1.0,
                     module_s={"jit__replay_windows": 1.0})
    red.op_s = {f"replay.replay_trace:%{n}": 0.1 * (i + 1)
                for i, n in enumerate(by_scope.values())}
    red.op_s["replay.replay_trace:%not_in_program.1"] = 5.0
    got = H.scope_seconds(red)
    want = {sc: pytest.approx(0.1 * (i + 1))
            for i, sc in enumerate(by_scope)}
    assert got == want
    # a module that did not run in the window is not read
    red2 = TR.Reduced(window_s=1.0, busy_s=1.0, module_s={"jit_other": 1.0},
                      op_s=dict(red.op_s))
    assert H.scope_seconds(red2) == {}
    # a program that declares no scopes: nothing to read
    monkeypatch.setattr(H, "declared_scopes", lambda: ())
    red3 = TR.Reduced(window_s=1.0, busy_s=1.0, op_s=dict(red.op_s),
                      module_s=dict(red.module_s))
    assert H.scope_seconds(red3) == {}
