"""The fixed-rate qpack encode kernel of a lane demotion against its HBM
roofline, in percent: the least time its bytes take at the chip's HBM
bandwidth (values and reciprocal scales read, codes written, from shapes;
``bench/counts.py``) over the kernel's device time in the trace. The XLA
passes around the kernel (plane transpose, scales, byte conversion) are not
counted."""
import counts


def read(facts, red, peaks):
    t, n = red.ops_matching("%qpack_encode_2d")
    if n == 0 or t <= 0:
        return None
    rows = counts.lane_demote_encode_rows(facts)
    least = n * counts.qpack_encode_bytes(rows, facts["d_model"]
                                          // facts["heads"],
                                          facts["kv_bits"]) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
