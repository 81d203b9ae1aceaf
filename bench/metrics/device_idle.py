"""Device: share of the traced window in which no op ran."""
import trace_reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return trace_reduce.idle_share(ctx.trace) * 100.0
