"""Operations and bytes of the served programs, from their shapes and
the configuration file's sizes (not from the program).

The layer kinds come from the published keys the file carries: latent
attention (MLA) where it has ``kv_lora_rank``, routed experts where it
has ``n_routed_experts`` (on the layers ``moe_layer`` names), and
grouped-query attention and a dense SwiGLU MLP otherwise.

``params_per_token`` counts the weights one decode token multiplies:
attention projections (for MLA the absorbed form decode runs: ``wq``,
``wkv_a``, ``wk_b`` folded into the query, ``wv_b`` after the weighted
sum, ``wo``), the MLP and the output head; the embedding lookup is no
product.  An expert layer counts its router, its shared experts and the
routed experts a token is expected to reach here: ``num_experts_per_tok``
of the ``n_routed_experts_published`` the router chooses among, of which
this chip holds ``n_routed_experts`` (one chip's share of an
expert-parallel deployment), so ``k x held / published`` experts on
average.  Attention over a context is counted apart.
"""

from __future__ import annotations

from typing import Any, Dict


def mla(conf: Dict[str, Any]) -> bool:
    """Whether the model attends through a latent (MLA) cache row."""
    return conf.get("kv_lora_rank") is not None


def moe_layer(conf: Dict[str, Any], i: int) -> bool:
    """Whether decoder layer ``i`` routes experts: the published rule,
    from ``first_k_dense_replace`` on, every ``moe_layer_freq``-th."""
    return (conf.get("n_routed_experts") is not None
            and i >= conf.get("first_k_dense_replace", 0)
            and i % conf.get("moe_layer_freq", 1) == 0)


def _heads(conf: Dict[str, Any]):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return d, H, conf.get("head_dim") or d // H


def _attn_proj(conf: Dict[str, Any]) -> int:
    d, H, hd = _heads(conf)
    if mla(conf):
        r, rope = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
        nope, v = conf["qk_nope_head_dim"], conf["v_head_dim"]
        return (d * H * (nope + rope) + d * (r + rope) + H * nope * r
                + H * r * v + H * v * d)
    Hk = conf["num_key_value_heads"]
    return d * H * hd + 2 * d * Hk * hd + H * hd * d


def _mlp(conf: Dict[str, Any], i: int):
    d = conf["hidden_size"]
    if not moe_layer(conf, i):
        return 3 * d * conf["intermediate_size"]
    ff = conf["moe_intermediate_size"]
    held = conf["n_routed_experts"]
    published = conf.get("n_routed_experts_published", held)
    shared = 3 * d * conf.get("n_shared_experts", 0) * ff
    routed = conf["num_experts_per_tok"] * held * 3 * d * ff / published
    return d * published + shared + routed


def params_per_token(conf: Dict[str, Any]):
    return (sum(_attn_proj(conf) + _mlp(conf, i)
                for i in range(conf["num_hidden_layers"]))
            + conf["hidden_size"] * conf["vocab_size"])


def attn_flops_per_key(conf: Dict[str, Any]) -> int:
    """Attention FLOPs of one query token against one key, one layer:
    scores plus the weighted sum (for MLA over the latent row: the
    ``kv_lora_rank`` part twice, the shared rotary key once)."""
    d, H, hd = _heads(conf)
    if mla(conf):
        return 2 * H * (2 * conf["kv_lora_rank"] + conf["qk_rope_head_dim"])
    return 4 * H * hd


def attend_cost(conf: Dict[str, Any], B: int, nmax: int, chunk: int
                ) -> Dict[str, float]:
    """One call of the served attend (``_attend_pooled``, or for MLA
    ``_attend_pooled_mla``) over B sequences of ``nmax`` gathered chunks:
    FLOPs, and the bytes it has to read (the gathered bf16 rows and the
    weights applied after the weighted sum: ``wo``, and MLA's ``wv_b``)."""
    d, H, hd = _heads(conf)
    S = nmax * chunk + 1
    if mla(conf):
        r, v = conf["kv_lora_rank"], conf["v_head_dim"]
        row = r + conf["qk_rope_head_dim"]
        flops = B * (S * attn_flops_per_key(conf) + 2 * H * r * v
                     + 2 * H * v * d)
        nbytes = 2 * (B * nmax * chunk * row + H * r * v + H * v * d)
    else:
        Hk = conf["num_key_value_heads"]
        flops = B * (S * attn_flops_per_key(conf) + 2 * H * hd * d)
        nbytes = 2 * (B * nmax * chunk * 2 * Hk * hd + H * hd * d)
    return {"flops": float(flops), "bytes": float(nbytes)}
