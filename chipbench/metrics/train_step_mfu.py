"""Whole DASHA-PP round's share of the chips' bf16 peak: model FLOPs of
the rounds in the traced window (forward and backward of both MVR
gradient evaluations, remat not counted) over the window's length."""


def read(ctx):
    if not ctx.units or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.counts["model_flops"] / ctx.trace.window_s / (
        ctx.chips * ctx.peaks["flops_bf16"])
