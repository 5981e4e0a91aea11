"""Device milliseconds a round spends in the DASHA-PP dispatch, lines
7-11 of Algorithm 1 (``repro.phase.dasha_dispatch``: the shard_map, the
f32 flatten and pad, the update kernels, the gathers and scatters), per
chip."""
from chipbench import phases


def read(ctx):
    s = phases.phase_s(ctx.trace, "dasha_dispatch")
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
