"""Device milliseconds a fused serve pass spends under the paged
attention kernel's scope (``repro.kernel.paged_attention_batched``),
over all layers."""
from chipbench import trace_reduce

KERNELS = ("paged_attention_batched",)


def read(ctx):
    s = trace_reduce.scope_s(ctx.trace, KERNELS)
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
