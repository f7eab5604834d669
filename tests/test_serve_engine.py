"""Serving-engine integration tests: continuous batching, preemption
(demotion), resume (promotion), second-chance victim selection, output
consistency under preemption, the batched scheduler's host-sync contract
(one sync per decode step), shadowed lane re-preemption (zero bytes), and
the padded-prefill regression (padded rows must decode identically to
unpadded ones)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import ServeConfig
from repro.configs import get_reduced
from repro.models import decode as D
from repro.models import transformer as T
from repro.serve.engine import Engine, DONE
from repro.serve.serial import SerialEngine

CFG = get_reduced("llama3_8b")
KEY = jax.random.PRNGKey(0)
SCFG = ServeConfig(max_running=2, hot_window=16, attn_chunk=32,
                   kv_rate_bits=8)


@pytest.fixture(scope="module")
def params():
    return T.init_params(KEY, CFG)[0]


def _prompt(seed, n=20):
    return list(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n))


def test_single_request_completes(params):
    eng = Engine(CFG, SCFG, params, max_len=128)
    rid = eng.submit(_prompt(0), max_new_tokens=8)
    eng.run_until_done()
    assert eng.requests[rid].state == DONE
    assert len(eng.result(rid)) == 8
    assert all(0 <= t < CFG.vocab_size for t in eng.result(rid))


def test_oversubscription_preempts_and_finishes(params):
    eng = Engine(CFG, SCFG, params, max_len=128)
    rids = [eng.submit(_prompt(i), max_new_tokens=6) for i in range(5)]
    eng.run_until_done(max_steps=400)
    for rid in rids:
        assert eng.requests[rid].state == DONE, rid
        assert len(eng.result(rid)) == 6
    # 5 requests through 2 lanes must have demoted someone
    assert eng.counters["demotions"] >= 1
    assert eng.counters["promotions"] >= 5


def test_preemption_through_qpack_kernel(params):
    """Lane demotion routed through the Pallas qpack encode (interpret
    mode off-TPU, the compiled kernel on a TPU) inside the jitted demote:
    same tokens and same parked codes as the jnp quantizer."""
    runs = {}
    for impl in ("kernel", "jnp"):
        eng = Engine(CFG, dataclasses.replace(SCFG, quantize_impl=impl),
                     params, max_len=128)
        rids = [eng.submit(_prompt(i), max_new_tokens=6) for i in range(3)]
        eng.run_until_done(max_steps=400)
        assert eng.counters["demotions"] >= 1
        assert all(eng.requests[r].state == DONE for r in rids)
        runs[impl] = eng
    k, j = runs["kernel"], runs["jnp"]
    assert k.counters == j.counters
    for rid in k.requests:
        assert k.result(rid) == j.result(rid)
    np.testing.assert_array_equal(np.asarray(k.cache["k_codes"]),
                                  np.asarray(j.cache["k_codes"]))


def test_preemption_consistency(params):
    """A request preempted mid-decode continues from its compressed KV; its
    tokens must match an uninterrupted run (8-bit KV is near-lossless for the
    argmax at these scales)."""
    # both engines use lanes=1 so the compiled programs (and bf16 reduction
    # orders) are identical — only the preemption differs
    scfg1 = ServeConfig(max_running=1, hot_window=16, attn_chunk=32,
                        kv_rate_bits=8)
    base = Engine(CFG, scfg1, params, max_len=128)
    r0 = base.submit(_prompt(42), max_new_tokens=10)
    base.run_until_done()
    want = base.result(r0)

    eng = Engine(CFG, scfg1, params, max_len=128)
    ra = eng.submit(_prompt(42), max_new_tokens=10)
    # interleave a competitor so ra gets preempted at least once
    for _ in range(3):
        eng.step()
    rb = eng.submit(_prompt(7), max_new_tokens=4)
    eng.run_until_done(max_steps=400)
    assert eng.requests[ra].state == DONE
    assert eng.requests[rb].state == DONE
    got = eng.result(ra)
    assert len(got) == len(want)
    # tokens generated BEFORE the first preemption must match exactly (ra ran
    # >= 3 steps before rb arrived). After resume the whole context is 8-bit
    # (the bf16 ring was demoted), and an untrained model's argmax margins
    # are razor-thin, so the tail may legitimately diverge — on a *trained*
    # model the quantization noise is far below the logit margins.
    assert got[:3] == want[:3], (got, want)
    assert all(0 <= t < CFG.vocab_size for t in got)


def test_resume_moves_zero_kv_bytes(params):
    eng = Engine(CFG, SCFG, params, max_len=128)
    rids = [eng.submit(_prompt(i), max_new_tokens=6) for i in range(4)]
    eng.run_until_done(max_steps=400)
    if eng.counters["demotions"]:
        # preempt parks the compressed payload only (the ring is quantized
        # on device first); resume installs the same compressed bytes
        assert eng.counters["resume_bytes"] >= 0
        assert eng.counters["preempt_bytes"] > 0


def test_one_host_sync_per_decode_step(params):
    """The host-sync contract: lane bookkeeping advances on device, and the
    host fetches exactly one (tokens, done, ref) triple per decode step."""
    eng = Engine(CFG, SCFG, params, max_len=128)
    for i in range(3):
        eng.submit(_prompt(i), max_new_tokens=8)
    eng.run_until_done(max_steps=400)
    assert eng.counters["steps"] > 0
    assert eng.counters["step_syncs"] == eng.counters["steps"]


def test_shadow_repreempt_moves_zero_bytes(params):
    """§4.5 at request granularity: re-preempting a resumed request that has
    not generated a new token re-validates the shadow — zero bytes move. And
    because KV is append-only, the shadow's prefix never goes stale: after N
    new tokens a preempt moves only the N-token suffix, not the context."""
    scfg1 = ServeConfig(max_running=1, hot_window=16, attn_chunk=32,
                        kv_rate_bits=8)
    eng = Engine(CFG, scfg1, params, max_len=128)
    rid = eng.submit(_prompt(3), max_new_tokens=12)
    for _ in range(3):
        eng.step()
    req = eng.requests[rid]
    pos0 = req.pos
    eng._preempt(0)
    first = eng.counters["preempt_bytes"]
    assert first > 0
    eng.queue.remove(rid)
    eng.lane_req[0] = rid
    eng._resume(req, 0)
    assert req.parked is not None and req.shadow_pos == req.pos
    eng._preempt(0)                       # untouched since resume
    assert eng.counters["preempt_bytes"] == first
    assert eng.counters["shadow_repreempts"] == 1
    # resume again, generate two tokens -> the shadow covers all but the
    # 2-token suffix; the next preempt pays exactly that delta
    eng.queue.remove(rid)
    eng.lane_req[0] = rid
    eng._resume(req, 0)
    eng.step()
    eng.step()
    assert req.parked is not None and req.shadow_pos == req.pos - 2
    eng._preempt(0)
    delta = eng.counters["preempt_bytes"] - first
    per_tok = first // pos0               # compressed bytes per parked token
    assert first == per_tok * pos0
    assert 0 < delta < first
    assert delta == 2 * per_tok


def test_padded_prefill_matches_exact(params):
    """Regression for the left-pad bug: a short prompt right-padded into a
    length bucket must produce the same logits and the same cache semantics
    as the unpadded prefill (padded positions used to enter the attended
    range as garbage KV)."""
    S, L = 12, 32                          # S < hot_window < L
    prompt = np.asarray(_prompt(9, n=S), np.int32)
    lg_e, c_e = D.prefill(params, {"tokens": jnp.asarray(prompt[None, :])},
                          CFG, SCFG, 128)
    padded = np.zeros((1, L), np.int32)
    padded[0, :S] = prompt
    lg_p, c_p = D.prefill(params, {"tokens": jnp.asarray(padded)}, CFG, SCFG,
                          128, lens=jnp.asarray([S]))
    assert np.array_equal(np.asarray(lg_e), np.asarray(lg_p))
    assert np.array_equal(np.asarray(c_e["cold_len"]),
                          np.asarray(c_p["cold_len"]))
    # decode from both caches with the same compiled step: identical tokens
    import functools
    step = jax.jit(functools.partial(D.decode_step, cfg=CFG, scfg=SCFG))

    def decode(cache, tok0):
        toks = []
        t = jnp.asarray([tok0], jnp.int32)
        p = jnp.asarray([S], jnp.int32)
        for _ in range(6):
            lg, cache = step(params, cache, t, p)
            t = jnp.argmax(lg, -1).astype(jnp.int32)
            p = p + 1
            toks.append(int(t[0]))
        return toks

    t0 = int(jnp.argmax(lg_e[0]))
    assert decode(c_e, t0) == decode(c_p, t0)


def test_batched_engine_matches_serial_engine(params):
    """The batched scheduler is a pure restructuring: same model, same decode
    step, same victim policy — generations must match the per-lane baseline
    token for token, across mixed prompt lengths and preemptions."""
    prompts = [_prompt(i, n=n) for i, n in enumerate((16, 12, 32, 20, 16))]

    def serve(engine_cls):
        eng = engine_cls(CFG, SCFG, params, max_len=128)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_done(max_steps=400)
        assert all(eng.requests[r].state == DONE for r in rids)
        return eng, [eng.result(r) for r in rids]

    se, got_s = serve(SerialEngine)
    be, got_b = serve(Engine)
    assert got_s == got_b
    # both engines demoted someone (5 requests through 2 lanes) and counted
    # the same honest byte unit (compressed payload per parked token)
    assert se.counters["demotions"] >= 1 and be.counters["demotions"] >= 1
    assert be.counters["step_syncs"] == be.counters["steps"]


def test_modeled_time_prices_motion_and_syncs(params):
    """Delivered-time accounting (DESIGN.md §12): the engine converts its
    preempt/resume byte and host-sync counters into modeled seconds —
    per-expander on a fabric-striped config, reconciling with the
    expander_stats byte totals, and monotone in the demotion traffic."""
    from repro.simx import time as TM

    eng = Engine(CFG, dataclasses.replace(SCFG, n_expanders=2), params,
                 max_len=128)
    rids = [eng.submit(_prompt(i), max_new_tokens=6) for i in range(5)]
    eng.run_until_done(max_steps=400)
    assert all(eng.requests[r].state == DONE for r in rids)
    assert eng.counters["demotions"] >= 1

    m = eng.modeled_time()
    assert len(m["motion_s_per_expander"]) == 2
    assert m["modeled_s"] > 0 and m["modeled_s_per_step"] > 0
    assert m["modeled_s"] == pytest.approx(
        m["sync_s"] + max(m["motion_s_per_expander"]))
    # sync term: one CXL round trip per host sync
    syncs = eng.counters["step_syncs"] + eng.counters["admit_syncs"]
    assert m["sync_s"] == pytest.approx(syncs * TM.DeviceConfig().cxl_lat)
    # motion term reconciles with the per-expander byte stats
    recomputed = TM.serve_motion_time(
        np.asarray(eng.expander_stats["preempt_bytes"], np.float64),
        np.asarray(eng.expander_stats["resume_bytes"], np.float64),
        TM.stack_devices([TM.DeviceConfig()] * 2, xp=np))
    assert list(recomputed) == m["motion_s_per_expander"]
    # a slower fleet can only cost more
    m_gen4 = eng.modeled_time(devices=TM.DEVICE_PROFILES["gen4"])
    assert m_gen4["modeled_s"] >= m["modeled_s"]
