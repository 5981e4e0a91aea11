"""Device milliseconds a round spends in the DASHA-PP commit, lines
12/19 of Algorithm 1 (``repro.phase.dasha_commit``: the f32 round trips
over g, g_i and h_i), per chip."""
from chipbench import phases


def read(ctx):
    s = phases.phase_s(ctx.trace, "dasha_commit")
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
