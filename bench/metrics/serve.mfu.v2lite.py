"""Model FLOPs of the traced window over the window's length times the
chip's bf16 peak, in percent. The FLOPs know latent attention and the
experts a token is routed to (``bench/counts_mla_moe.py``): per prefilled
prompt token, the expanded attention projections, the dense layer's or
the router's, 6 routed and 2 shared experts' matrices, and causal
attention over its prefix; per decode token the absorbed projections and
attention over its context in the latent space; the output head once per
prompt and per decode token."""
import counts_mla_moe


def read(facts, red, peaks):
    if "kv_lora" not in facts:
        return None
    flops = counts_mla_moe.model_flops(facts)
    if flops <= 0 or red.window_s <= 0:
        return None
    return 100.0 * flops / (red.window_s * peaks["bf16_flops"])
