"""Operations and bytes of the served programs, from their shapes and
the configuration file's sizes (not from the program).

``params_per_token`` counts the weights one token multiplies in a
forward pass: attention projections, the SwiGLU MLP and the output
head; the embedding lookup is no product.  Attention over a context is
counted apart.
"""

from __future__ import annotations

from typing import Any, Dict


def _attn_proj(conf: Dict[str, Any]) -> int:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    Hk = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    return d * H * hd + 2 * d * Hk * hd + H * hd * d


def params_per_token(conf: Dict[str, Any]) -> int:
    per_layer = _attn_proj(conf) + 3 * conf["hidden_size"] \
        * conf["intermediate_size"]
    return (conf["num_hidden_layers"] * per_layer
            + conf["hidden_size"] * conf["vocab_size"])


def attn_flops_per_key(conf: Dict[str, Any]) -> int:
    """Attention FLOPs of one query token against one key, one layer:
    scores plus the weighted sum."""
    H = conf["num_attention_heads"]
    hd = conf.get("head_dim") or conf["hidden_size"] // H
    return 4 * H * hd


def attend_cost(conf: Dict[str, Any], B: int, nmax: int, chunk: int
                ) -> Dict[str, float]:
    """One call of the served attend (``_attend_pooled``) over B
    sequences of ``nmax`` gathered chunks: FLOPs, and the bytes it has to
    read (the gathered bf16 rows and the output projection's weights)."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    Hk = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // H
    S = nmax * chunk + 1
    flops = B * (S * attn_flops_per_key(conf) + 2 * H * hd * d)
    nbytes = 2 * (B * nmax * chunk * 2 * Hk * hd + H * hd * d)
    return {"flops": float(flops), "bytes": float(nbytes)}
