"""Per-round readings of the program's own decode-round spans and
counters: the ``<phase>_s`` self times, ``compiles`` and ``total_s`` that
``repro.serving.tracing`` adds to each ``round_profiles`` entry.  A
program without them reads None."""


def mean_ms(profiles, phases):
    """Mean over the window's rounds of the summed self times of
    ``phases``, in ms."""
    keys = [f"{p}_s" for p in phases]
    if not profiles or any(k not in profiles[0] for k in keys):
        return None
    return sum(r[k] for r in profiles for k in keys) / len(profiles) * 1e3


def untraced_ms(profiles):
    """Mean over the window's rounds of the round body's ``total_s`` less
    the self times of every phase inside it, in ms: what no span covers."""
    try:
        from repro.serving.tracing import DECODE_SPANS, OUTSIDE_TOTAL
    except ImportError:
        return None
    inside = [p for p in DECODE_SPANS if p not in OUTSIDE_TOTAL]
    spanned = mean_ms(profiles, inside)
    if spanned is None:
        return None
    return sum(r["total_s"] for r in profiles) / len(profiles) * 1e3 - spanned


def compiles(profiles):
    """Backend compiles on the decode thread over the window's rounds."""
    if not profiles or "compiles" not in profiles[0]:
        return None
    return sum(r["compiles"] for r in profiles)
