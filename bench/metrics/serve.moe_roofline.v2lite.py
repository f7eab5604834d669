"""The decode step's grouped expert products against their roofline, in
percent: the least time they could take (the larger of their FLOPs at the
chip's bf16 peak and their bytes at its HBM bandwidth: each expert's three
matrices read once per call, the rows' activations in and out;
``bench/counts_mla_moe.py``), summed over the window's decode steps and
MoE layers, over the device self time of the step's ops under the scope
``moe.experts`` (``bench/decode_scopes.py``). Null where the program has
no such scope."""
import counts_mla_moe
import decode_scopes


def read(facts, red, peaks):
    if "kv_lora" not in facts or not facts["steps"]:
        return None
    t = decode_scopes.decode_scope_seconds(red).get("moe.experts", 0.0)
    if t <= 0:
        return None
    flops, nbytes = counts_mla_moe.expert_call(
        counts_mla_moe.decode_expert_rows(facts), facts)
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    calls = facts["steps"] * (facts["layers"] - facts["first_k_dense"])
    return 100.0 * calls * least / t
