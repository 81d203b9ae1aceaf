"""Scheduler: mean number of sequences in a decode round of the window
(sampled by the harness at every scheduler step that decoded)."""


def read(ctx):
    b = [n for _, _, n in ctx.rounds if n > 0]
    return sum(b) / len(b) if b else None
