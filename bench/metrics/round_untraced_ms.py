"""Engine decode round: the round body's time (``round_profiles``
total_s) that none of the program's spans covers, per round, in ms."""
import round_spans


def read(ctx):
    return round_spans.untraced_ms(ctx.round_profiles)
