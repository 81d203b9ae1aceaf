"""90th percentile of every gap between consecutive output tokens of a
request that ends in the window, in ms."""
import window


def read(ctx):
    g = window.gaps(ctx.stamps, ctx.t0, ctx.t1)
    return window.pct(g, 90) * 1e3 if g else None
