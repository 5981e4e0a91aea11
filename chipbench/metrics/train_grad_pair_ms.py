"""Device milliseconds a round spends in the variant's gradient pair,
the model's forward and backward at x^{t+1} and x^t
(``repro.phase.grad_pair``), per chip."""
from chipbench import phases


def read(ctx):
    s = phases.phase_s(ctx.trace, "grad_pair")
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
