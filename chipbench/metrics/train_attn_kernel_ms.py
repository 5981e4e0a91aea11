"""Device milliseconds a round spends under the training attention's
flash-kernel scope (``repro.kernel.flash_attention``: the forward, dq
and dkv kernels with their padding and head relayouts), per chip."""
from chipbench import trace_reduce

KERNELS = ("flash_attention",)


def read(ctx):
    s = trace_reduce.scope_s(ctx.trace, KERNELS)
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
