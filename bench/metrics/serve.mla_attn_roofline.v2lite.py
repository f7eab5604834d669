"""The decode step's latent attention against its HBM roofline, in
percent: the least time of the bytes it needs (for every decode token of
the window, its live context once: the older tokens as 4-bit latent codes
and scales, the newest from the bf16 hot ring; ``bench/counts_mla_moe.py``),
summed over the MLA layers, over the device self time of the step's ops
under the scope ``mla.attend`` (``bench/decode_scopes.py``). Null where the
program has no such scope."""
import counts_mla_moe
import decode_scopes


def read(facts, red, peaks):
    if "kv_lora" not in facts or not facts.get("decode_ctx"):
        return None
    t = decode_scopes.decode_scope_seconds(red).get("mla.attend", 0.0)
    if t <= 0:
        return None
    nbytes = facts["layers"] * sum(
        counts_mla_moe.mla_decode_attn_bytes(c, facts)
        for c in facts["decode_ctx"])
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / t
