"""Operations and bytes of an MLA + MoE model (DeepSeek-V2) computed from
shapes, for its utilization and roofline metrics. Nothing here is
measured; the times they are divided by come from the device trace.

``facts`` carries the shapes under the form's names: ``layers``,
``first_k_dense``, ``d_model``, ``heads``, ``nope``, ``rope``, ``v_dim``,
``kv_lora``, ``d_ff`` (the dense layers' MLP), ``experts``, ``top_k``,
``shared``, ``expert_d_ff``, ``vocab``; for the cache ``lanes``,
``max_len``, ``hot_window``, ``kv_bits``; for the window
``prefill_lens`` and ``decode_ctx`` (the context of every decode token)."""
from __future__ import annotations


def mla_params(f: dict, absorbed: bool) -> int:
    """Weights one token multiplies through in one MLA block: the query,
    the latent (``W_kv_a``) and output projections, and either the key and
    value expansion of its own latent (``W_kv_b``, prefill) or, absorbed
    (decode), ``W_UK`` into the query and ``W_UV`` out of the attended
    latent, which are the same count."""
    d, h = f["d_model"], f["heads"]
    q = d * h * (f["nope"] + f["rope"])
    kv_a = d * (f["kv_lora"] + f["rope"])
    kv_b = f["kv_lora"] * h * (f["nope"] + f["v_dim"])
    return q + kv_a + kv_b + h * f["v_dim"] * d


def mlp_params(f: dict, dense: bool) -> int:
    """Weights one token multiplies through in one layer's MLP: the dense
    SwiGLU, or the router, its ``top_k`` routed experts and the shared
    experts."""
    d = f["d_model"]
    if dense:
        return 3 * d * f["d_ff"]
    return d * f["experts"] + (f["top_k"] + f["shared"]) * 3 * d * \
        f["expert_d_ff"]


def token_params(f: dict, absorbed: bool) -> int:
    k = f["first_k_dense"]
    return f["layers"] * mla_params(f, absorbed) + k * mlp_params(f, True) \
        + (f["layers"] - k) * mlp_params(f, False)


def prefill_flops(prompt: int, f: dict) -> int:
    """One prompt's prefill: every token through every layer, attention
    expanded per head (scores over nope + rope dims, values over v dims)
    over each token's causal prefix, and the output head for the last
    token only."""
    keys = prompt * (prompt + 1) // 2
    attn = 2 * f["heads"] * (f["nope"] + f["rope"] + f["v_dim"]) * keys
    return 2 * token_params(f, False) * prompt + f["layers"] * attn \
        + 2 * f["d_model"] * f["vocab"]


def decode_flops(keys: int, f: dict) -> int:
    """One decode token over ``keys`` cached tokens, absorbed: per head,
    scores over the whole latent (kv_lora + rope) and values over its
    kv_lora part."""
    attn = 2 * f["heads"] * (2 * f["kv_lora"] + f["rope"]) * keys
    return 2 * token_params(f, True) + f["layers"] * attn \
        + 2 * f["d_model"] * f["vocab"]


def model_flops(facts: dict) -> int:
    """Model FLOPs of a serve window: the prompts prefilled in it and the
    context of every decode token it produced."""
    return sum(prefill_flops(n, facts) for n in facts["prefill_lens"]) + \
        sum(decode_flops(c, facts) for c in facts["decode_ctx"])


def expert_call(rows: int, f: dict):
    """(FLOPs, HBM bytes) of one layer's grouped expert products over
    ``rows`` (token, expert) pairs: the three products' multiply-adds; each
    expert's three bf16 matrices read once, the rows' activations read in
    and the results written out (bf16). The intermediate rows and the
    router are not counted."""
    d, ff = f["d_model"], f["expert_d_ff"]
    flops = 2 * rows * 3 * d * ff
    nbytes = f["experts"] * 3 * d * ff * 2 + 2 * rows * d * 2
    return flops, nbytes


def decode_expert_rows(f: dict) -> int:
    """Pairs in one decode step's expert layer: every lane's token to its
    ``top_k`` experts (the step runs all lanes)."""
    return f["lanes"] * f["top_k"]


def mla_decode_attn_bytes(keys: int, f: dict) -> int:
    """HBM bytes one decode token's latent attention needs in one layer over
    its ``keys`` cached tokens: the newest ``hot_window`` of them once from
    the bf16 hot ring, the older ones once as 4-bit codes and an f32 scale
    a token. The program reads and masks the whole ``max_len`` codes
    region; what lies past the context is not counted."""
    r = f["kv_lora"] + f["rope"]
    hot = min(keys, f["hot_window"])
    return (keys - hot) * (r * f["kv_bits"] // 8 + 4) + hot * r * 2


def latent_demote_encode_rows(f: dict) -> int:
    """Blocks in the qpack encode of one latent lane demotion: every hot-ring
    token of every layer, one block of ``kv_lora + rope`` values each."""
    return f["layers"] * f["hot_window"]
