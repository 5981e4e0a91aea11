"""Share of the HBM roofline the DASHA-PP update kernels reach: the
bytes one node's update needs (counts.dasha_update_bytes, at the stored
dtypes) at the chip's HBM bandwidth, over the kernels' device time.
The update is memory-bound: its operations are a few per byte."""
from chipbench import trace_reduce

KERNELS = ("dasha_", "block_")


def read(ctx):
    s = trace_reduce.scope_s(ctx.trace, KERNELS)
    if not s:
        return None
    return 100.0 * ctx.counts["dasha_bytes"] / ctx.peaks[
        "hbm_bytes_per_s"] / s
