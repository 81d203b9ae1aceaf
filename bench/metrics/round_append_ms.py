"""Tier store: the new tokens' K/V appended to the store (the program's
``leoam.append`` span, self time, its device->host reads excluded) per
round, in ms."""
import round_spans


def read(ctx):
    return round_spans.mean_ms(ctx.round_profiles, ("leoam.append",))
