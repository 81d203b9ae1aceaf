"""Engine decode round: host chunk selection (``round_profiles.eval_s``)
per round, in ms."""


def read(ctx):
    p = ctx.round_profiles
    return sum(r["eval_s"] for r in p) / len(p) * 1e3 if p else None
