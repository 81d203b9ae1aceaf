"""Engine decode round: the per-round slice of each scanned layer's
stacked weights (the program's ``leoam.weights`` span, self time) per
round, in ms."""
import round_spans


def read(ctx):
    return round_spans.mean_ms(ctx.round_profiles, ("leoam.weights",))
