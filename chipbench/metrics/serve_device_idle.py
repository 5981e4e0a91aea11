"""Share of the traced serving window in which no operation runs on the
device."""
from chipbench import trace_reduce


def read(ctx):
    w = ctx.trace.window_s
    if w <= 0 or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(ctx.trace) / w)
