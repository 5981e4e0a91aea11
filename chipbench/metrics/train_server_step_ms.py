"""Device milliseconds a round spends in the server step, line 5 of
Algorithm 1, x^{t+1} = x^t - gamma g^t and the cast back to the
parameters' dtype (``repro.phase.server_step``), per chip."""
from chipbench import phases


def read(ctx):
    s = phases.phase_s(ctx.trace, "server_step")
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
