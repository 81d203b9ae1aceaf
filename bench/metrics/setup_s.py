"""Process start to window start: JAX start, weights, compilation or
cache loads, the cell's warm-up."""


def read(ctx):
    return ctx.setup_s
