"""Device milliseconds a round spends under the DASHA-PP update and
BlockRandK kernel scopes (``repro.kernel.dasha_*``, ``repro.kernel.
block_*``), per chip."""
from chipbench import trace_reduce

KERNELS = ("dasha_", "block_")


def read(ctx):
    s = trace_reduce.scope_s(ctx.trace, KERNELS)
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
