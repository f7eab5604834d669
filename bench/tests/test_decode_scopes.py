"""Device op time of the serve decode step charged to the program's serve
scopes (``decode_scopes.py``): only the decode phase's ops, only the
decode-step program's names, and the grouped products (``ragged-dot-*``,
which carry no scope of their own on a TPU) charged to ``moe.experts``."""
import dataclasses

import jax
import pytest

import decode_scopes as DS
import hlo_scopes as H
import trace_reduce as TR


def _red(op_s):
    return TR.Reduced(window_s=1.0, busy_s=1.0,
                      module_s={"jit_serve_decode_step": 1.0,
                                "jit_serve_prefill": 1.0}, op_s=op_s)


def test_decode_phase_ops_by_scope(monkeypatch):
    names = {"fusion.1": "mla.attend", "fusion.2": "moe.route",
             "ragged-dot-none": H.UNSCOPED, "ragged-dot-none.2": H.UNSCOPED,
             "copy.3": H.UNSCOPED}
    seen = []

    def live(mods, scopes):
        seen.append((list(mods), tuple(scopes)))
        return dict(names)
    monkeypatch.setattr(H, "live_scopes", live)
    red = _red({"serve.decode:%fusion.1": 1.0,
                "serve.decode:%fusion.2": 0.25,
                "serve.decode:%ragged-dot-none": 2.0,
                "serve.decode:%ragged-dot-none.2": 0.5,
                "serve.decode:%copy.3": 0.125,
                "serve.decode:%not_in_program": 9.0,
                "serve.prefill:%ragged-dot-none": 7.0,
                "serve.prefill:%fusion.1": 7.0})
    got = DS.decode_scope_seconds(red)
    assert got == {"mla.attend": 1.0, "moe.route": 0.25,
                   "moe.experts": 2.5, H.UNSCOPED: 0.125}
    assert seen[0][0] == ["jit_serve_decode_step"]
    assert "moe.experts" in seen[0][1]
    # a trace without the decode-step program reads nothing
    red2 = TR.Reduced(window_s=1.0, busy_s=1.0,
                      module_s={"jit_serve_prefill": 1.0},
                      op_s=dict(red.op_s))
    assert DS.decode_scope_seconds(red2) == {}


@pytest.fixture(scope="module")
def decode_program():
    """Run a few engine steps of the MLA + MoE model at its reduced size;
    the live decode-step program's names by serve scope."""
    from repro.common.types import ServeConfig
    from repro.configs import get_reduced
    from repro.models import transformer as T
    from repro.obs.spans import SERVE_SCOPES
    from repro.serve import Engine
    cfg = dataclasses.replace(get_reduced("deepseek_v2_lite"),
                              dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)[0]
    eng = Engine(cfg, ServeConfig(max_running=2, hot_window=8,
                                  attn_chunk=32), params, max_len=64)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    eng.run_until_done(max_steps=10)
    return H.live_scopes([DS.MODULE], SERVE_SCOPES)


def test_the_decode_program_carries_the_attention_and_expert_scopes(
        decode_program):
    found = set(decode_program.values())
    assert {"mla.attend", "moe.experts", "moe.route", "moe.shared"} <= found
