"""Tier store: device-pool hits over lookups in the window
(``pool_stats()`` deltas)."""


def read(ctx):
    h, m = ctx.pool_hits, ctx.pool_misses
    return h / (h + m) if h + m else None
