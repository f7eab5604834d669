"""deepseek-v2-lite [moe]: latent attention without q-LoRA and YaRN RoPE;
one dense layer, then 26 layers of 64 routed experts (softmax, greedy
top-6, weights not renormalised) and 2 shared experts
[hf:deepseek-ai/DeepSeek-V2-Lite; hf]."""
from repro.common.types import (MLAConfig, ModelConfig, MoEConfig, YaRNConfig,
                                replace)

YARN = YaRNConfig(factor=40.0, original_max_position_embeddings=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707)

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=10944, vocab_size=102400,
    max_seq_len=163840, norm_eps=1e-6, attn_kind="mla", first_k_dense=1,
    moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                  num_shared_experts=2, norm_topk_prob=False,
                  routed_scaling_factor=1.0),
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, yarn=YARN))

REDUCED = replace(
    CONFIG, num_layers=3, d_model=128, num_heads=4, num_kv_heads=4,
    d_ff=256, vocab_size=512, max_seq_len=4096,
    moe=MoEConfig(num_experts=16, top_k=6, expert_d_ff=64,
                  num_shared_experts=2, norm_topk_prob=False),
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16, yarn=YARN))
