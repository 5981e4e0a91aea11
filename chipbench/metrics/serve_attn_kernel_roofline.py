"""Share of its roofline the paged attention kernel reaches: the larger
of its operations at the bf16 peak and its bytes (K and V of the live
pages each slot's table covers, at the pool's dtype, plus q and out) at
the HBM bandwidth, over the kernel scope's device time."""
from chipbench import trace_reduce

KERNELS = ("paged_attention_batched",)


def read(ctx):
    s = trace_reduce.scope_s(ctx.trace, KERNELS)
    if not s:
        return None
    bound = max(ctx.counts["attn_flops"] / ctx.peaks["flops_bf16"],
                ctx.counts["attn_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / s
