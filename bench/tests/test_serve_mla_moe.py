"""The MLA + MoE serve form end to end at a size a CPU test run can hold
(the look for a chip skipped): a sound run is correct, with no compile in
the window and a parked request's latent codes compared; a run whose
answers are altered where they are produced is not. And its operation
and byte counts against hand-computed values for one layer."""
import pytest

import counts_mla_moe as C
import run

CELL = "serve.deepseek_v2_lite.longctx"


def small_cell(seed: int = 20240611):
    run.check_program()
    cell = run.load_cell(CELL, seed)
    cell.config.update(hidden_size=128, num_attention_heads=2,
                       num_key_value_heads=2, intermediate_size=256,
                       moe_intermediate_size=64, n_routed_experts=8,
                       kv_lora_rank=32, qk_nope_head_dim=16,
                       qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
                       num_hidden_layers=3)
    cell.config["serve"].update(max_running=4, max_len=256, hot_window=32,
                                attn_chunk=128, prefill_budget=128)
    cell.mix["prompt"].update(lo=40, hi=120, median=60)
    cell.mix["output"].update(lo=4, hi=24, median=10)
    cell.mix.update(trace_seconds=1, check_tokens=40, check_requests=2)
    return cell


def _altered(form):
    form_setup = form.setup

    def setup():
        form_setup()
        eng = form.eng
        step_fn = eng._step_fn

        def broken(params, cache, state, **kw):
            cache2, state2, done = step_fn(params, cache, state, **kw)
            return cache2, dict(state2, tok=(state2["tok"] + 1)
                                % form.mcfg.vocab_size), done
        eng._step_fn = broken
    form.setup = setup


@pytest.mark.parametrize("fault", [None, "altered"])
def test_mla_moe_serve_run(fault):
    out = run.run_cell(small_cell(), 3.0, False, require_chip=False,
                       form_hook=_altered if fault else None)
    if fault:
        assert not out["correct"], out["checks"]
        return
    assert out["correct"], out["checks"]
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert out["checks"]["kv_code_steps"]["value"] is not None
    assert "widest_logit_gap" not in out["checks"]
    assert out["info"]["program_widest_gap"] >= 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0


SHAPE = dict(layers=3, first_k_dense=1, d_model=8, heads=2, nope=3, rope=2,
             v_dim=4, kv_lora=5, d_ff=16, experts=4, top_k=2, shared=1,
             expert_d_ff=6, vocab=10, lanes=3, max_len=32, hot_window=4,
             kv_bits=4)


def test_mla_params():
    # q 8*2*5 = 80; kv_a 8*7 = 56; kv_b 5*2*7 = 70; o 2*4*8 = 64
    assert C.mla_params(SHAPE, absorbed=False) == 270
    assert C.mla_params(SHAPE, absorbed=True) == 270


def test_mlp_params_and_token_params():
    # dense 3*8*16 = 384; moe router 8*4 = 32 + (2 + 1) * 3*8*6 = 432
    assert C.mlp_params(SHAPE, dense=True) == 384
    assert C.mlp_params(SHAPE, dense=False) == 464
    assert C.token_params(SHAPE, False) == 3 * 270 + 384 + 2 * 464


def test_prefill_and_decode_flops():
    tp = 3 * 270 + 384 + 2 * 464                       # 2122
    # 3 tokens: 2*2122*3; attention keys 6, per layer 2*2*(3+2+4)*6 = 216;
    # head 2*8*10 = 160
    assert C.prefill_flops(3, SHAPE) == 2 * tp * 3 + 3 * 216 + 160
    # one token over 5 keys: per layer 2*2*(2*5+2)*5 = 240
    assert C.decode_flops(5, SHAPE) == 2 * tp + 3 * 240 + 160
    facts = dict(SHAPE, prefill_lens=[3], decode_ctx=[5, 5])
    assert C.model_flops(facts) == C.prefill_flops(3, SHAPE) + \
        2 * C.decode_flops(5, SHAPE)


def test_expert_and_latent_bytes():
    # 6 rows: 2*6*3*8*6 FLOPs; 4 experts * 3*8*6 * 2 B + 2*6*8*2 B
    assert C.expert_call(6, SHAPE) == (1728, 1152 + 192)
    assert C.decode_expert_rows(SHAPE) == 6
    # 10 keys: 6 cold as codes (7*4/8 -> 3 B + a 4 B scale), 4 hot in the
    # ring (7 * 2 B); 3 keys: all in the ring
    assert C.mla_decode_attn_bytes(10, SHAPE) == 6 * 7 + 4 * 14
    assert C.mla_decode_attn_bytes(3, SHAPE) == 3 * 14
    # a latent demotion encodes 3 layers * 4 ring tokens, one block each
    assert C.latent_demote_encode_rows(SHAPE) == 12


def _reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py")


PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}


def test_latent_qpack_roofline_reader():
    import trace_reduce as TR
    read = _reader("serve.qpack_roofline.v2lite").read
    red = TR.Reduced(window_s=1.0, busy_s=1.0,
                     op_s={"serve.park:%qpack_encode_2d.1": 1e-6},
                     op_n={"serve.park:%qpack_encode_2d.1": 2})
    # 2 calls of 12 blocks of 7 values: 12 * (7 * 4 + 4) B read and
    # 12 * 7 * 4 / 8 = 42 B of codes written each
    want = 100.0 * 2 * (12 * 32 + 42) / PEAKS["hbm_bytes_per_s"] / 1e-6
    assert read(SHAPE, red, PEAKS) == pytest.approx(want)
    assert read(SHAPE, TR.Reduced(window_s=1.0, busy_s=1.0), PEAKS) is None
    no_mla = {k: v for k, v in SHAPE.items() if k != "kv_lora"}
    assert read(no_mla, red, PEAKS) is None


def test_latent_attention_roofline_counts_live_context(monkeypatch):
    import decode_scopes
    read = _reader("serve.mla_attn_roofline.v2lite").read
    monkeypatch.setattr(decode_scopes, "decode_scope_seconds",
                        lambda red: {"mla.attend": 1e-6})
    facts = dict(SHAPE, steps=1, decode_ctx=[10, 3])
    want = 100.0 * 3 * (98 + 42) / PEAKS["hbm_bytes_per_s"] / 1e-6
    assert read(facts, None, PEAKS) == pytest.approx(want)
    assert read(dict(facts, decode_ctx=[]), None, PEAKS) is None
