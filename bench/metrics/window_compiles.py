"""Compiler: backend compiles (or persistent-cache loads) on the decode
thread in the window's rounds (the program's ``compiles`` counter)."""
import round_spans


def read(ctx):
    return round_spans.compiles(ctx.round_profiles)
