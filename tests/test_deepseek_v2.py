"""DeepSeek-V2-Lite's serve path against its plain reference
(``bench/reference/deepseek_v2.py``) at the ``REDUCED`` size, on seeded
random weights:

  * prefill logits (right-padded rows of different lengths);
  * prefill, then decode through the compressed latent cache with a hot
    window small enough that 4-bit codes are read, against the reference's
    full forward at the same cold boundaries;
  * the dropless expert layer against a per-token loop over its top-6
    experts, under a router that sends every token to one expert;
  * a prefill split by the token budget gives the tokens and the cache of
    one call;
  * preempt/resume through ``Engine`` gives the tokens of an uninterrupted
    run;
  * the YaRN frequencies and ``mscale`` against the published formulas.

The model computes in float32 here (the weights hold bfloat16 values, as
on the chip), so that the tolerances speak of the mathematics and not of
bfloat16 rounding; the chip's comparison at the cell's size is the
benchmark's ``correct``.
"""
import dataclasses
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import ServeConfig
from repro.configs import get_config, get_reduced
from repro.models import decode as D
from repro.models import layers as L
from repro.models import moe as MOE
from repro.serve.engine import DONE, Engine

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from forms.serve import make_weights  # noqa: E402
from reference import deepseek_v2 as ref  # noqa: E402
from reference import llm  # noqa: E402

CFG = dataclasses.replace(get_reduced("deepseek_v2_lite"), dtype="float32")
W = 8          # hot window: a decode query past 8 tokens reads codes


def file_config(cfg) -> dict:
    """The configuration file's keys (as in bench/configs/) of ``cfg``."""
    m, mo, y = cfg.mla, cfg.moe, cfg.mla.yarn
    return {"num_attention_heads": cfg.num_heads,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "qk_nope_head_dim": m.qk_nope_head_dim,
            "qk_rope_head_dim": m.qk_rope_head_dim,
            "v_head_dim": m.v_head_dim, "kv_lora_rank": m.kv_lora_rank,
            "num_experts_per_tok": mo.top_k,
            "norm_topk_prob": mo.norm_topk_prob,
            "routed_scaling_factor": mo.routed_scaling_factor,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_hidden_layers": cfg.num_layers,
            "rope_scaling": dataclasses.asdict(y)}


@pytest.fixture(scope="module")
def params():
    return make_weights(CFG, 7)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).tolist()


def test_prefill_logits_match_reference(params):
    lens = [37, 64, 50]
    S = 64
    toks = np.zeros((len(lens), S), np.int32)
    prompts = [_prompt(i, n) for i, n in enumerate(lens)]
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    scfg = ServeConfig(hot_window=W, attn_chunk=32, kv_rate_bits=4)
    logits, _ = jax.jit(lambda p, b, l: D.prefill(p, b, CFG, scfg, 128, l))(
        params, {"tokens": jnp.asarray(toks)}, jnp.asarray(lens, jnp.int32))
    c = file_config(CFG)
    for i, p in enumerate(prompts):
        want, _ = ref.forward(params, p, np.zeros(len(p), np.int32), c,
                              rows=[len(p) - 1], pad_to=64)
        np.testing.assert_allclose(np.asarray(logits[i]), want[0],
                                   atol=2e-3, rtol=0)


def test_prefill_then_decode_reads_codes_like_the_reference(params):
    scfg = ServeConfig(hot_window=W, attn_chunk=32, kv_rate_bits=4)
    max_len, n_prompt, n_new = 64, 20, 12
    prompt = _prompt(3, n_prompt)
    logits, cache = jax.jit(lambda p, b: D.prefill(p, b, CFG, scfg, max_len))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    step = jax.jit(lambda p, c, t, pos: D.decode_step(p, c, t, pos, CFG,
                                                      scfg))
    seq, got = list(prompt), [np.asarray(logits[0])]
    for _ in range(n_new):
        seq.append(int(np.argmax(got[-1])))
        logits, cache = step(params, cache, jnp.asarray([seq[-1]]),
                             jnp.asarray([len(seq) - 1], jnp.int32))
        got.append(np.asarray(logits[0]))
    assert int(cache["cold_len"][0, 0]) > n_prompt - W   # codes were read
    cold = llm.cold_boundaries(n_prompt, len(seq), [], W)
    want, lats = ref.forward(params, seq, cold, file_config(CFG),
                             rows=np.arange(n_prompt - 1, len(seq)),
                             pad_to=64, keep_lat=True)
    # 4-bit codes below the cold boundary on both sides, so only rounding
    # is left: the program's ring holds bf16 latents, and a latent value
    # near a code boundary can take the neighbouring code (seen: 0.047);
    # a reference that leaves the codes out lies 0.23-0.49 away
    np.testing.assert_allclose(np.stack(got), want, atol=0.1, rtol=0)
    # the codes themselves: within one quantization step of the reference
    pos = int(cache["cold_len"][0, 0])
    for li, lat in enumerate(lats):
        assert llm.code_error(np.asarray(cache["lat_codes"][li, 0, :pos]),
                              np.asarray(cache["lat_scales"][li, 0, :pos]),
                              lat[:pos]) < 1.0


def test_dropless_expert_layer_matches_per_token_loop(params):
    mp = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.float32),
                                params["moe"])
    # every token sends a pair to expert 3 (its logit raised by about 6
    # over the others', which keep weights of their own): a capacity
    # dispatch would drop most of expert 3's pairs
    d = CFG.d_model
    mp["router"] = mp["router"].at[:, 3].add(6.0 / d)
    x = 1.0 + jax.random.normal(jax.random.PRNGKey(1), (2, 24, d))
    out = np.asarray(MOE.moe_apply_dropless(mp, x, CFG))
    mo = CFG.moe
    p = jax.tree_util.tree_map(np.asarray, mp)

    def swiglu(v, w):
        g, u = v @ w["wg"], v @ w["wi"]
        return (g / (1 + np.exp(-g)) * u) @ w["wo"]
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            v = np.asarray(x[b, t], np.float64)
            logit = v @ p["router"]
            prob = np.exp(logit - logit.max())
            prob /= prob.sum()
            top = np.argsort(-prob)[:mo.top_k]
            assert 3 in top
            want = sum(prob[e] * swiglu(v, {k: p[k][e] for k in
                                            ("wg", "wi", "wo")})
                       for e in top) + swiglu(v, p["shared"])
            np.testing.assert_allclose(out[b, t], want, atol=1e-4, rtol=1e-4)


def _engine(params, budget, lanes=4, window=W, max_len=96):
    scfg = ServeConfig(max_running=lanes, hot_window=window, attn_chunk=32,
                       kv_rate_bits=4, prefill_budget=budget)
    return Engine(CFG, scfg, params, max_len=max_len)


def test_prefill_budget_split_gives_one_calls_tokens_and_cache(params):
    prompts = [_prompt(10 + i, n) for i, n in enumerate((40, 60, 33, 64))]
    engines = []
    for budget in (0, 128):             # one call of 4 x 64; two of 2 x 64
        eng = _engine(params, budget)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.step()
        engines.append((eng, rids, jax.device_get(eng.cache)))
    (one, r1, c1), (split, r2, c2) = engines
    assert one.counters["prefill_batches"] == 1
    assert split.counters["prefill_batches"] == 2
    for k in c1:
        np.testing.assert_allclose(np.asarray(c1[k], np.float32),
                                   np.asarray(c2[k], np.float32),
                                   atol=1e-5, err_msg=k)
    for eng, _, _ in engines:
        eng.run_until_done(max_steps=50)
    assert [one.result(r) for r in r1] == [split.result(r) for r in r2]


def test_preempt_resume_gives_the_uninterrupted_tokens(params):
    # a one-token hot window: every earlier token is already in codes, so
    # a park's demotion of the ring writes exactly the codes the streaming
    # eviction would have, and the resumed request must decode the same
    # tokens as one never preempted
    prompts = [_prompt(20 + i, 30 + 5 * i) for i in range(3)]
    solo = _engine(params, 0, lanes=2, window=1)
    want = []
    for p in prompts:
        rid = solo.submit(p, max_new_tokens=10)
        solo.run_until_done(max_steps=100)
        want.append(solo.result(rid))
    eng = _engine(params, 0, lanes=2, window=1)
    rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_done(max_steps=200)
    assert eng.counters["demotions"] >= 1 and eng.counters["promotions"] > 3
    assert all(eng.requests[r].state == DONE for r in rids)
    assert [eng.result(r) for r in rids] == want


def _hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """HF DeepseekV2YarnRotaryEmbedding, transcribed."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / \
            (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def test_yarn_frequencies_and_mscale_follow_the_published_formulas():
    cfg = get_config("deepseek_v2_lite")
    y = cfg.mla.yarn
    want = _hf_yarn_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    np.testing.assert_allclose(L.yarn_freqs(64, 10000.0, y), want, rtol=1e-6)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(64, 10000.0, dataclasses.asdict(y)), want,
        rtol=1e-6)
    # the fast dims keep their frequencies, the slow ones are divided by 40
    assert L.yarn_freqs(64, 10000.0, y)[0] == 1.0
    np.testing.assert_allclose(L.yarn_freqs(64, 10000.0, y)[-1],
                               10000.0 ** (-62 / 64) / 40, rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert L.yarn_mscale(40.0, 0.707) == pytest.approx(mscale)
    assert L.mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    assert ref.softmax_scale(file_config(cfg)) == \
        pytest.approx(192 ** -0.5 * mscale ** 2)
