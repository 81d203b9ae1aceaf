"""Kernel: the served attend's share of its roofline.  For every call in
the window, the least time the chip could take (the larger of its FLOPs
over peak FLOP/s and its bytes over HBM bandwidth, from its shapes),
summed, over the device time of the attend programs in the trace."""
import flops
import trace_reduce


def _is_attend(name):
    return "attend_pooled" in trace_reduce.program_name(name)


def read(ctx):
    if ctx.trace is None or not ctx.attend_calls:
        return None
    dev = trace_reduce.program_s(ctx.trace, _is_attend)
    if dev <= 0:
        return None
    least = 0.0
    for B, nmax in ctx.attend_calls:
        c = flops.attend_cost(ctx.conf, B, nmax, ctx.chunk)
        least += max(c["flops"] / ctx.peaks["bf16_flops"],
                     c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return least / dev * 100.0
