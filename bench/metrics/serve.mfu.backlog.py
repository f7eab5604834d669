"""Model FLOPs of the traced window (prefilled prompt tokens and decode
tokens: 2 x matmul weights per token, plus attention over each token's
context; ``bench/counts.py``) over the window's length times the chip's
bf16 peak, in percent."""
import counts


def read(facts, red, peaks):
    flops = counts.model_flops(facts)
    if flops <= 0 or red.window_s <= 0:
        return None
    return 100.0 * flops / (red.window_s * peaks["bf16_flops"])
