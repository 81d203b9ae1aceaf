"""Model step: decode FLOPs in the traced window against the chip's bf16
peak over that window.  Per decoded token 2 x the weights it multiplies,
plus attention over the keys the selections gathered (chunks selected x
chunk size)."""
import flops


def read(ctx):
    if ctx.trace is None or not ctx.decode_tokens or ctx.trace.window_s <= 0:
        return None
    f = (2.0 * flops.params_per_token(ctx.conf) * ctx.decode_tokens
         + flops.attn_flops_per_key(ctx.conf) * ctx.selected_chunks
         * ctx.chunk)
    return f / ctx.trace.window_s / ctx.peaks["bf16_flops"] * 100.0
