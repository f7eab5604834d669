"""Operation and byte counts against hand-computed values."""
import counts

SHAPE = dict(layers=2, d_model=8, heads=2, kv_heads=2, d_ff=16, vocab=10)


def test_layer_params():
    # q,k,v,o: 4 * 8*8 = 256; swiglu: 3 * 8*16 = 384
    assert counts.layer_matmul_params(8, 2, 2, 16) == 640


def test_prefill_flops():
    # 3 tokens: matmul 2*640*2*3 = 7680; attention keys 1+2+3 = 6 ->
    # 4*8*6*2 = 384; head for the last token 2*8*10 = 160
    assert counts.prefill_flops(3, **SHAPE) == 7680 + 384 + 160


def test_decode_flops_and_window():
    # one token over 5 keys: 2*640*2 = 2560; 4*8*5*2 = 320; head 160
    assert counts.decode_flops(5, **SHAPE) == 2560 + 320 + 160
    facts = dict(SHAPE, prefill_lens=[3], decode_ctx=[5, 5])
    assert counts.model_flops(facts) == 8224 + 2 * 3040


def test_qpack_bytes():
    # 4 blocks of 128 f32 values: 2048 B read + 16 B scales + 256 B codes
    assert counts.qpack_encode_bytes(4, 128, 4) == 2048 + 16 + 256
    assert counts.lane_demote_encode_rows(
        {"layers": 8, "hot_window": 256, "kv_heads": 32}) == 65536
