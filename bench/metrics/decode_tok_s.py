"""Output tokens completed in the window over the window's seconds."""
import window


def read(ctx):
    return window.rate(ctx.stamps, ctx.t0, ctx.t1)
