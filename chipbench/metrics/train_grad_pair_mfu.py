"""The gradient pair's share of the chips' bf16 peak: model FLOPs of the
traced rounds (forward and backward of both MVR gradient evaluations,
remat not counted) over the device time under
``repro.phase.grad_pair``."""
from chipbench import phases


def read(ctx):
    s = phases.phase_s(ctx.trace, "grad_pair")
    if not s:
        return None
    return 100.0 * ctx.counts["model_flops"] / s / (
        ctx.chips * ctx.peaks["flops_bf16"])
