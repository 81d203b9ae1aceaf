"""Engine decode round: tier fetch (``round_profiles`` gather_s +
upload_s) per round, in ms."""


def read(ctx):
    p = ctx.round_profiles
    if not p:
        return None
    return sum(r["gather_s"] + r["upload_s"] for r in p) / len(p) * 1e3
