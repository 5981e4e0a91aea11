"""Device milliseconds a round spends in ops under no
``repro.phase.`` scope (the step's scalar metrics, copies the compiler
adds), per chip; nothing where the program has no phase scopes."""
from chipbench import phases


def read(ctx):
    s = phases.unscoped_s(ctx.trace)
    if s is None or not ctx.units:
        return None
    return 1000.0 * s / ctx.units
