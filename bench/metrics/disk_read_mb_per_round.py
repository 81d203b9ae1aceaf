"""Tier store: fp16 MB promoted disk -> host per decode round in the
window.  The store bills each chunk at its codec-scaled size; the count
of chunks times the fp16 chunk size is what moved."""
import window


def read(ctx):
    n = len(ctx.round_profiles)
    if not n:
        return None
    return window.fp16_bytes(ctx.disk_host_billed, ctx.billed_per_chunk,
                             ctx.chunk_bytes) / n / 1e6
