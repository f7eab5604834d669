"""The fixed-rate qpack encode kernel of a latent lane demotion against
its HBM roofline, in percent: the least time its bytes take at the chip's
HBM bandwidth (every hot-ring token of every layer as one block of
``kv_lora + rope`` values read in f32 with its reciprocal scale, the
packed codes written; ``bench/counts.py``, ``bench/counts_mla_moe.py``)
over the kernel's device time in the trace. The XLA passes around the
kernel are not counted. Null where no such kernel ran."""
import counts
import counts_mla_moe


def read(facts, red, peaks):
    if "kv_lora" not in facts:
        return None
    t, n = red.ops_matching("%qpack_encode_2d")
    if n == 0 or t <= 0:
        return None
    least = n * counts.qpack_encode_bytes(
        counts_mla_moe.latent_demote_encode_rows(facts),
        facts["kv_lora"] + facts["rope"], facts["kv_bits"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
