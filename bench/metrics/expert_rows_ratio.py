"""Experts: rows the held experts multiplied per (token, expert) pick
that landed on them, over the window (the program's ``moe_rows`` and
``moe_picks_held`` counters, summed over the window's rounds).  A
dispatch that multiplies each expert over its own rows only reads 1; a
drop-free dispatch that gives every held expert one slot per token reads
about published experts / experts per token."""


def read(ctx):
    p = [r for r in ctx.round_profiles if "moe_rows" in r]
    picks = sum(r["moe_picks_held"] for r in p)
    return sum(r["moe_rows"] for r in p) / picks if picks else None
