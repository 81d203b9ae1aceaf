"""Engine decode round: picking each scanned layer's stacked weights and
repeat index, or a recurrent layer's slice (the program's
``leoam.weights`` span, self time) per round, in ms."""
import round_spans


def read(ctx):
    return round_spans.mean_ms(ctx.round_profiles, ("leoam.weights",))
