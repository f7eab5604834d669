"""Operations and bytes computed from shapes, for the roofline and
utilization metrics. Nothing here is measured; the times they are divided
by come from the device trace."""
from __future__ import annotations


def layer_matmul_params(d_model: int, heads: int, kv_heads: int,
                        d_ff: int) -> int:
    """Weights one token multiplies through in one decoder layer: the q, k,
    v and output projections and the three SwiGLU matrices."""
    hd = d_model // heads
    return d_model * (heads * hd) * 2 + d_model * (kv_heads * hd) * 2 \
        + 3 * d_model * d_ff


def attention_flops(d_model: int, layers: int, keys: int) -> int:
    """QK^T and PV for one query over ``keys`` keys, every layer."""
    return 4 * d_model * keys * layers


def prefill_flops(prompt: int, *, layers, d_model, heads, kv_heads, d_ff,
                  vocab) -> int:
    """One prompt's prefill: every prompt token through every layer, causal
    attention over its prefix, and the output head for the last token only
    (the server computes just that row)."""
    p = layer_matmul_params(d_model, heads, kv_heads, d_ff)
    keys = prompt * (prompt + 1) // 2
    return 2 * p * layers * prompt + attention_flops(d_model, layers, keys) \
        + 2 * d_model * vocab


def decode_flops(keys: int, *, layers, d_model, heads, kv_heads, d_ff,
                 vocab) -> int:
    """One decode token attending over ``keys`` keys (its context)."""
    p = layer_matmul_params(d_model, heads, kv_heads, d_ff)
    return 2 * p * layers + attention_flops(d_model, layers, keys) \
        + 2 * d_model * vocab


def model_flops(facts: dict) -> int:
    """Model FLOPs of a serve window: the prompts prefilled in it and the
    context of every decode token it produced."""
    shape = {k: facts[k] for k in ("layers", "d_model", "heads", "kv_heads",
                                   "d_ff", "vocab")}
    return sum(prefill_flops(n, **shape) for n in facts["prefill_lens"]) + \
        sum(decode_flops(c, **shape) for c in facts["decode_ctx"])


def qpack_encode_bytes(rows: int, block: int, bits: int,
                       in_bytes: int = 4) -> int:
    """HBM bytes of one fixed-rate qpack encode over ``rows`` blocks of
    ``block`` values: the values and the reciprocal scales read, the packed
    codes written. The scales the wrapper computes in XLA are not counted."""
    return rows * block * in_bytes + rows * 4 + rows * block * bits // 8


def lane_demote_encode_rows(facts: dict) -> int:
    """Blocks in one of the two (K, V) encodes of a lane demotion: every
    hot-ring token of every layer and KV head."""
    return facts["layers"] * facts["hot_window"] * facts["kv_heads"]
