"""Engine decode round: host time enqueuing the round's device work (the
program's ``leoam.qkv``, ``leoam.attend``, ``leoam.mlp`` and
``leoam.logits`` spans, self times) per round, in ms."""
import round_spans

PHASES = ("leoam.qkv", "leoam.attend", "leoam.mlp", "leoam.logits")


def read(ctx):
    return round_spans.mean_ms(ctx.round_profiles, PHASES)
