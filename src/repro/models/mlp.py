"""Feed-forward blocks: dense (SwiGLU / GeGLU / squared-ReLU / ReLU) and
mixture-of-experts with sort-based static-shape dispatch (EP-friendly).

MoE dispatch avoids the O(T·E·C) GShard one-hot tensor: tokens are argsorted
by expert id, ranked within their expert, and scattered into (E, C) slots —
index arrays only, static shapes, capacity drops are explicit.  Expert
matmuls run as (E, C, d) einsums with the expert dim sharded over the
``model``/EP axis.

A layer may hold one share of the experts (``MoECfg.router_width``,
``first_expert``): it routes over every published expert and computes
only the picks that land on the experts it holds.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import activation
from repro.models.params import ParamDef
from repro.sharding.ctx import constrain


def _gated(act: str) -> bool:
    return act in ("swiglu", "geglu")


def _act_fn(act: str):
    return {"swiglu": jax.nn.silu, "geglu":
            lambda x: jax.nn.gelu(x, approximate=True)}.get(act) or activation(act)


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def dense_params(cfg: ArchConfig, ff: Optional[int] = None) -> Dict[str, ParamDef]:
    d = cfg.d_model
    ff = ff or cfg.d_ff
    p = {"w_up": ParamDef((d, ff), ("embed", "ffn")),
         "w_down": ParamDef((ff, d), ("ffn", "embed"))}
    if _gated(cfg.act):
        p["w_gate"] = ParamDef((d, ff), ("embed", "ffn"))
    return p


def dense_apply(p, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    h = x @ p["w_up"]
    if _gated(cfg.act):
        h = _act_fn(cfg.act)(x @ p["w_gate"]) * h
    else:
        h = _act_fn(cfg.act)(h)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------


def moe_params(cfg: ArchConfig) -> Dict[str, ParamDef]:
    assert cfg.moe is not None
    m, d = cfg.moe, cfg.d_model
    ff = m.d_ff_expert
    p = {
        "router": ParamDef((d, m.n_routed), ("embed", None), dtype="float32"),
        "w_up": ParamDef((m.n_experts, d, ff), ("expert", "embed", None)),
        "w_down": ParamDef((m.n_experts, ff, d), ("expert", None, "embed")),
    }
    if _gated(cfg.act):
        p["w_gate"] = ParamDef((m.n_experts, d, ff), ("expert", "embed", None))
    if m.n_shared:
        sp = dense_params(cfg, ff=m.n_shared * ff)
        p.update({f"shared_{k}": v for k, v in sp.items()})
    return p


def _capacity(tokens: int, m) -> int:
    c = int(math.ceil(tokens * m.top_k / m.n_routed * m.capacity_factor))
    return max(4, c)


#: the dispatch counts ``moe_apply(..., counts=True)`` returns, in order
MOE_COUNTS = ("moe_picks_held", "moe_rows", "moe_experts_hit")


def moe_apply(p, cfg: ArchConfig, x: jax.Array, *, no_drop: bool = False,
              counts: bool = False):
    """x: (B, S, d) -> (B, S, d), aux metrics (load-balance/z losses).

    Routing: a float32 softmax over all ``m.n_routed`` experts the router
    scores, greedy top-k, the weights renormalised over the k picks only
    under ``m.norm_topk``.  Picks that land on the experts held here
    (``m.first_expert`` on, ``m.n_experts`` of them) are dispatched to
    their slots; picks on experts held elsewhere add nothing here.  The
    shared experts are added once; the aux losses see the whole router.

    ``counts=True`` also returns an int32 vector in :data:`MOE_COUNTS`
    order: (token, expert) picks on held experts, rows the held experts
    multiplied (slots, including empty ones) and held experts with at
    least one pick.

    ``no_drop=True`` is the INFERENCE dispatch: expert capacity is raised
    to the worst case (every token to one expert) so no token is ever
    capacity-dropped.  Training keeps the standard capacity-factor drops,
    but drops depend on the token count T — a serving path that splits one
    prompt across prefill chunks (or pads it to a length bucket) would
    route identical tokens differently at different T, breaking
    chunked-vs-whole token identity.  With no_drop each token's output
    depends only on that token, so any chunking/padding of the same prompt
    produces bitwise-identical rows (chunked admission also keeps the
    (E, T, d) dispatch buffer small, since T is the chunk size).

    Dispatch is PER SEQUENCE (batch row): the argsort/rank/scatter all run
    along the row axis, and the batch dim is data-sharded — so token
    routing never communicates.  A single flattened (B·S·K) sort made XLA
    emit a *distributed* sort (~1 TiB of all-reduce/collective-permute per
    step on the MoE train cells; §Perf B1).  Capacity is per row.
    """
    # NOTE §Perf B (deepseek train_4k hillclimb): three dispatch
    # reformulations were measured against this implementation and ALL
    # regressed on the compiled-HLO terms —
    #   B1 per-row argsort:        coll 22.6->21.0 s but mem 24.3->39.1 s,
    #                              peak 14.5->56 GiB;
    #   B2 pinned routing specs:   coll 131 s (resharding ping-pong);
    #   B3 sort-free cumsum rank:  same coll as B1, mem 35.9 s;
    #   B5 no-FSDP (pure EP/TP):   compiled flops x7, peak 75 GiB.
    # Root cause of the residual collective term is the FSDP layout
    # contracting expert matmuls over the data-sharded d dim plus the
    # per-microbatch expert-grad reductions; the proper fix (shard_map
    # local grad accumulation) is future work.
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K, R = m.n_experts, m.top_k, m.n_routed
    xf = x.reshape(T, d)

    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (T, R)
    top_p, top_e = jax.lax.top_k(probs, K)                    # (T, K)
    if m.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)     # renormalize

    C = T if no_drop else _capacity(T, m)
    # ---- sort-based dispatch ----
    e_loc = top_e - m.first_expert                            # held ids
    held = (e_loc >= 0) & (e_loc < E)
    e_flat = jnp.where(held, e_loc, E).reshape(-1)            # (T*K,); E: absent
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    # rank within expert: position - first-occurrence(expert)
    starts = jnp.searchsorted(sorted_e, jnp.arange(E + 1), side="left")
    rank = jnp.arange(T * K) - starts[sorted_e]
    keep = (rank < C) & (sorted_e < E)
    slot = jnp.where(keep, sorted_e * C + rank, E * C)        # overflow -> sink
    token_of = order // K

    # gather tokens into (E*C + 1, d) slots (last row = overflow sink)
    xe = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(xf[token_of])
    xe = xe[:-1].reshape(E, C, d)

    h = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    if _gated(cfg.act):
        g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
        h = _act_fn(cfg.act)(g) * h
    else:
        h = _act_fn(cfg.act)(h)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * C, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], 0)

    # combine: inverse permutation back to (T, K) slots
    slot_tk = jnp.zeros((T * K,), jnp.int32).at[order].set(slot.astype(jnp.int32))
    y_tk = ye[slot_tk].reshape(T, K, d)
    y = jnp.einsum("tkd,tk->td", y_tk.astype(jnp.float32),
                   top_p.astype(jnp.float32)).astype(x.dtype)

    if m.n_shared:
        sp = {k[len("shared_"):]: v for k, v in p.items() if k.startswith("shared_")}
        y = y + dense_apply(sp, cfg, xf)

    # aux losses (Switch-style load balance + router z-loss)
    onehot = jax.nn.one_hot(top_e, R, dtype=jnp.float32)       # (T,K,R)
    frac_tokens = jnp.mean(jnp.sum(onehot, 1), 0)              # f_e
    frac_probs = jnp.mean(probs, 0)                            # P_e
    lb_loss = R * jnp.sum(frac_tokens * frac_probs)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    # picks on experts held elsewhere are not dropped here
    kept = jnp.where(sorted_e < E, keep, True)
    dropped = 1.0 - jnp.mean(kept.astype(jnp.float32))
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss, "moe_drop_frac": dropped}
    if not counts:
        return y.reshape(B, S, d), aux
    per_expert = starts[1:] - starts[:-1]                      # picks per held
    n = jnp.stack([jnp.sum(held.astype(jnp.int32)), jnp.int32(E * C),
                   jnp.sum((per_expert > 0).astype(jnp.int32))])
    return y.reshape(B, S, d), aux, n


def moe_loss(aux: Dict[str, jax.Array], cfg: ArchConfig) -> jax.Array:
    m = cfg.moe
    return (m.aux_loss_weight * aux["moe_lb_loss"]
            + m.router_z_weight * aux["moe_z_loss"])
