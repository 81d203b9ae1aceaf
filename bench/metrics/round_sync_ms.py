"""Engine decode round: device->host reads on the decode thread (the
program's ``leoam.sync`` span, self time) per round, in ms."""
import round_spans


def read(ctx):
    return round_spans.mean_ms(ctx.round_profiles, ("leoam.sync",))
