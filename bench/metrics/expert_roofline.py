"""Kernel: the expert layers' post-attention program
(``jit_post_attention_moe``: residual add, norm, router, held and
shared experts) against its roofline.  For every call in the window, the
least time the chip could take, the larger of its FLOPs over peak FLOP/s
and its bytes over HBM bandwidth, summed, over the device time of that
program in the trace.

Per call, from the configuration's sizes and the program's counters
(``moe_rows``, ``moe_picks_held``, ``moe_experts_hit``, summed over a
round's expert layers): FLOPs 2 x (rows x (router + shared experts) +
picks on held experts x one expert's weights); bytes the norm scale and
the shared experts in bf16, the router in float32 (as the program keeps
it), the weights of the held experts that some row picked, in bf16, and
the bf16 activations (the two inputs and the output).  A round's counters
are summed over its calls, so each call is taken at the round's mean;
the sum of the calls' maxima is that, since every decode call lies on
the bytes side of the ridge (at most ``rows`` FLOPs a byte)."""
import flops
import trace_reduce

PROGRAM = "jit_post_attention_moe"


def call_cost(conf, rows, picks, hit):
    """FLOPs and bytes of one expert layer's post-attention call over
    ``rows`` tokens, ``picks`` of whose top-k picks land on held experts,
    ``hit`` held experts picked at least once."""
    d, ff = conf["hidden_size"], conf["moe_intermediate_size"]
    router = d * conf.get("n_routed_experts_published",
                          conf["n_routed_experts"])
    shared = 3 * d * ff * conf.get("n_shared_experts", 0)
    expert = 3 * d * ff
    return {"flops": 2.0 * (rows * (router + shared) + picks * expert),
            "bytes": (2.0 * (d + shared + hit * expert + 3 * rows * d)
                      + 4.0 * router)}


def read(ctx):
    p = [r for r in ctx.round_profiles if "moe_rows" in r]
    if ctx.trace is None or not p:
        return None
    dev = trace_reduce.program_s(
        ctx.trace, lambda n: trace_reduce.program_name(n) == PROGRAM)
    if dev <= 0:
        return None
    conf = ctx.conf
    calls = sum(flops.moe_layer(conf, i)
                for i in range(conf["num_hidden_layers"]))
    held = conf["n_routed_experts"]
    least = 0.0
    for r in p:
        c = call_cost(conf, r["moe_rows"] / calls / held,
                      r["moe_picks_held"] / calls,
                      r["moe_experts_hit"] / calls)
        least += calls * max(c["flops"] / ctx.peaks["bf16_flops"],
                             c["bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return least / dev * 100.0
