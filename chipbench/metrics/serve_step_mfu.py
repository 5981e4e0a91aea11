"""Fused serve passes' share of the chip's bf16 peak: 2·N per token fed
(prompt and decode), the head for each slot's logits row, and causal
attention over each token's live context, summed over the passes in the
traced window, over the window's length."""


def read(ctx):
    if not ctx.units or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.counts["flops"] / ctx.trace.window_s / (
        ctx.chips * ctx.peaks["flops_bf16"])
