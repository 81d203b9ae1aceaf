"""Plain reference of the served model and of LeoAM's sparse decode, in
float32 at HIGHEST precision.

It imports nothing of the program.  It reads the model's sizes and the
LeoAM selection rules from the benchmark's configuration file and its
weights through ``weights.layer`` (weights the benchmark made from the
seed), and runs the textbook computation:

* the prompt: a dense causal forward pass (RMSNorm, rotary embedding,
  attention, the MLP, the output head), layer by layer in query blocks,
  which gives the first served token's logits and each layer's keys and
  values;
* every later token, one step at a time through all layers: the query
  scores each chunk of ``chunk_size`` cached rows by the upper bound of
  q.k over the chunk's min/max box (over all the chunk's rows of the
  zero-initialised cache, as the program's store keeps them), the best
  chunks are taken up to the layer's token budget (``importance_rate``,
  ``early_rate`` on the first ``early_layers`` layers) by the
  branch-and-bound rule of the LeoAM paper, the sink, recent and hot
  chunks are added, and the token attends to the chosen chunks' keys and
  to itself.  The hot chunks are the ``hot_frac`` most used ones by a
  per-sequence use count that decays by ``hot_decay`` at every layer's
  selection.

The layer kinds follow the published keys the file carries:

* attention is grouped-query, or latent (MLA) where the file has
  ``kv_lora_rank``: ``q = h.wq`` per head, its rotary part rotated;
  ``h.wkv_a`` splits into the latent ``c_kv``, RMS-normed by
  ``kv_norm``, and one rotary key shared by every head; per head
  ``k = [c_kv.wk_b || k_rope]`` and ``v = c_kv.wv_b``, attention with
  scale ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``, then ``wo``.
  The cache row the boxes are taken over is the latent row
  ``[c_kv || k_rope]``, one box per chunk for all heads, and the
  selection query is the absorbed ``[q_nope.wk_b^T || q_rope]``, scaled,
  which scores a latent row as q.k scores that row's keys;
* the MLP is a dense SwiGLU of the weights' width, or on the layers
  ``flops.moe_layer`` names (``first_k_dense_replace`` on) experts
  (``_moe``): a float32 softmax router over all the published experts,
  greedy top-k, the experts held here and the shared ones.

Departures from the published models that the reference shares with the
program: rotary embedding rotates the first half of the rotated part
against the second (NeoX), over the whole rotated part, with no
``rope_scaling`` (no longrope, no yarn); the configuration files list
these under ``reduced`` or ``assumed``.

``mode="fp8"`` is the control: the same computation with both operands
of every product rounded to float8 e4m3 (activations per row, weights
and values per tensor), the precision step below the served bfloat16.
"""

from __future__ import annotations

import functools
import heapq
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import flops
import weights as W

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 1024


def _q8(x: jax.Array, axis) -> jax.Array:
    """Round to float8 e4m3 with an amax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


def _mm(a, b, mode: str, eq: str = "...k,kn->...n"):
    """Matrix product in f32; the control rounds both operands to fp8
    first (``a`` per row over its last axis, ``b`` per tensor)."""
    a, b = a.astype(F32), b.astype(F32)
    if mode == "fp8":
        a, b = _q8(a, -1), _q8(b, None)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """Rotate the first half of the last axis against the second half.
    x: (S, H, d); pos: (S,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None, None] * freqs
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _sizes(conf):
    H, Hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    return H, Hk, conf.get("head_dim") or conf["hidden_size"] // H


def _qkv(c, h, pos, conf, mode):
    """Rotated queries and keys, and values, of rows ``h`` at ``pos``, and
    the cache rows the boxes are taken over: for MLA the latent rows
    (``_qkv_mla``), for GQA the keys themselves (``None`` comes back)."""
    if flops.mla(conf):
        return _qkv_mla(c, h, pos, conf, mode)
    S = h.shape[0]
    H, Hk, hd = _sizes(conf)
    q = _mm(h, c["wq"], mode).reshape(S, H, hd)
    k = _mm(h, c["wk"], mode).reshape(S, Hk, hd)
    v = _mm(h, c["wv"], mode).reshape(S, Hk, hd)
    th = conf["rope_theta"]
    return _rope(q, pos, th), _rope(k, pos, th), v, None


def _qkv_mla(c, h, pos, conf, mode):
    """Latent attention in its textbook form: per-head queries (S, H,
    nope + rope), keys [c_kv.wk_b || k_rope] (S, H, nope + rope) and
    values c_kv.wv_b (S, H, v), and the latent rows [c_kv || k_rope]
    (S, 1, r + rope)."""
    if conf.get("q_lora_rank"):
        raise ValueError("the reference has no low-rank query projection "
                         "(q_lora_rank)")
    S = h.shape[0]
    H, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    nope, rope = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    th = conf["rope_theta"]
    q = _mm(h, c["wq"], mode).reshape(S, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, th)], -1)
    kv_a = _mm(h, c["wkv_a"], mode)
    c_kv = _rms(kv_a[:, :r], c["kv_norm"], conf["rms_norm_eps"])
    k_rope = _rope(kv_a[:, None, r:], pos, th)               # (S, 1, rope)
    k_nope = _mm(c_kv, c["wk_b"], mode, "sr,hrd->shd")
    v = _mm(c_kv, c["wv_b"], mode, "sr,hrd->shd")
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (S, H, rope))], -1)
    return q, k, v, jnp.concatenate([c_kv, k_rope[:, 0]], -1)[:, None]


def _swiglu(m, h, mode, prefix=""):
    g = _mm(h, m[prefix + "w_gate"], mode)
    u = _mm(h, m[prefix + "w_up"], mode)
    return _mm(jax.nn.silu(g) * u, m[prefix + "w_down"], mode)


def _moe(m, h, conf, mode):
    """One expert layer over rows ``h`` (S, d).  The router is a float32
    softmax over all ``n_routed_experts_published`` experts (where the
    file does not give that key, over ``n_routed_experts``), greedy top
    ``num_experts_per_tok``, renormalised only where ``norm_topk_prob``
    is set, scaled by ``routed_scaling_factor``.  The experts held here
    are ids 0 .. ``n_routed_experts`` - 1 (chip 0's share of an
    expert-parallel deployment): each computes its SwiGLU for every row,
    weighted by the row's routing weight on it, which is 0 where the
    row did not choose it; the weights on experts held elsewhere add
    nothing here.  The shared SwiGLU (``n_shared_experts`` x
    ``moe_intermediate_size`` wide) is added once."""
    held = conf["n_routed_experts"]
    published = conf.get("n_routed_experts_published", held)
    if m["router"].shape[-1] != published or m["w_up"].shape[0] != held:
        raise ValueError(
            f"expert weights hold {m['w_up'].shape[0]} experts under a "
            f"router over {m['router'].shape[-1]}; the configuration "
            f"states {held} of {published}")
    probs = jax.nn.softmax(_mm(h, m["router"], mode), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf.get("norm_topk_prob"):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p * conf.get("routed_scaling_factor", 1.0)
    gate = jnp.einsum("sk,ske->se", top_p, jax.nn.one_hot(top_e, held),
                      precision=HI)
    g = _mm(h, m["w_gate"], mode, "sd,edf->esf")
    u = _mm(h, m["w_up"], mode, "sd,edf->esf")
    y = _mm(jax.nn.silu(g) * u, m["w_down"], mode, "esf,efd->esd")
    out = jnp.einsum("se,esd->sd", gate, y, precision=HI)
    if conf.get("n_shared_experts"):
        out = out + _swiglu(m, h, mode, "shared_")
    return out


def _mlp(w, x, conf, mode, moe):
    h = _rms(x, w["ln2"], conf["rms_norm_eps"])
    if moe:
        return x + _moe(w["mlp"], h, conf, mode)
    return x + _swiglu(w["mlp"], h, mode)


def _causal(q, k, v, scale, mode):
    """Causal softmax attention.  q: (S, H, d); k: (S, Hk, d); v: (S, Hk,
    dv); query head h reads key head h // (H / Hk).  Query blocks of
    Q_BLOCK rows each read only the keys up to their last row."""
    S, H, _ = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    if mode == "fp8":
        k, v = _q8(k, -1), _q8(v, None)
    q = q * scale
    diag = (jnp.arange(Q_BLOCK)[None, :, None]
            >= jnp.arange(Q_BLOCK)[None, None, :])
    outs = []
    for i in range(S // Q_BLOCK):
        end = (i + 1) * Q_BLOCK
        qi = q[i * Q_BLOCK:end]
        if mode == "fp8":
            qi = _q8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k[:end], precision=HI)
        mask = jnp.concatenate(
            [jnp.ones((1, Q_BLOCK, end - Q_BLOCK), bool), diag], axis=-1)
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        if mode == "fp8":
            p = _q8(p, -1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[:end], precision=HI))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("conf_items", "mode", "moe"))
def _prefill_layer(w, x, pos, n, *, conf_items, mode: str, moe: bool):
    """One layer over the whole (padded) prompt row; returns the layer's
    output, its keys and values and, for MLA, its latent rows, zero past
    the prompt's ``n`` rows."""
    conf = dict(conf_items)
    c = w["core"]
    h = _rms(x, w["ln1"], conf["rms_norm_eps"])
    q, k, v, lat = _qkv(c, h, pos, conf, mode)
    o = _causal(q, k, v, 1.0 / math.sqrt(q.shape[-1]), mode)
    x = x + _mm(o.reshape(x.shape[0], -1), c["wo"], mode)
    live = (pos < n)[:, None, None]
    if lat is not None:
        lat = jnp.where(live, lat, 0.0)
    return _mlp(w, x, conf, mode, moe), jnp.where(live, k, 0.0), \
        jnp.where(live, v, 0.0), lat


@functools.partial(jax.jit, static_argnames=("conf_items", "mode"))
def _step_qkv(w, x, pos, *, conf_items, mode: str):
    """One decode token's query, key and value at one layer; for MLA also
    its latent row and the selection query, the absorbed
    [q_nope.wk_b^T || q_rope] over sqrt(nope + rope)."""
    conf = dict(conf_items)
    h = _rms(x, w["ln1"], conf["rms_norm_eps"])
    q, k, v, lat = _qkv(w["core"], h, pos[None], conf, mode)
    if lat is None:
        return q[0], k[0], v[0], None, None
    nope = conf["qk_nope_head_dim"]
    q_lat = _mm(q[0, :, :nope], w["core"]["wk_b"], mode, "hd,hrd->hr")
    sel = jnp.concatenate([q_lat, q[0, :, nope:]], -1) \
        * (1.0 / math.sqrt(q.shape[-1]))
    return q[0], k[0], v[0], lat[0], sel


@functools.partial(jax.jit, static_argnames=("conf_items", "mode", "moe"),
                   donate_argnums=(5, 6))
def _step_rest(w, x, q, k, v, kc, vc, pos, mask, *, conf_items, mode: str,
               moe: bool):
    """Write the token's key and value at ``pos``, attend to the rows
    ``mask`` marks (the chosen chunks' live rows and the token itself),
    then the output projection and the MLP."""
    conf = dict(conf_items)
    H, Hk, hd = q.shape[0], k.shape[0], q.shape[-1]
    kc = kc.at[pos].set(k)
    vc = vc.at[pos].set(v)
    kk, vv = kc, vc
    qq = q * (1.0 / math.sqrt(hd))
    if mode == "fp8":
        kk, vv, qq = _q8(kk, -1), _q8(vv, None), _q8(qq, -1)
    qg = qq.reshape(Hk, H // Hk, hd)
    s = jnp.einsum("kgd,tkd->kgt", qg, kk, precision=HI)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    if mode == "fp8":
        p = _q8(p, -1)
    o = jnp.einsum("kgt,tkd->kgd", p, vv, precision=HI).reshape(1, -1)
    x = x + _mm(o, w["core"]["wo"], mode)
    return _mlp(w, x, conf, mode, moe), kc, vc


@functools.partial(jax.jit, static_argnames=("conf_items", "mode"))
def _head(params, x, *, conf_items, mode: str):
    conf = dict(conf_items)
    h = _rms(x, params["final_norm"], conf["rms_norm_eps"])
    if conf["tie_word_embeddings"]:
        return _mm(h, params["embed"], mode, "sd,vd->sv")
    return _mm(h, params["lm_head"], mode)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _boxes(k, *, chunk: int):
    """Per-chunk elementwise max and min of the cache rows.  Rows past
    the prompt are zero, as the program's cache rows are where no token
    has been written: the store ingests every chunk up to ``max_len``, so
    a chunk that decode tokens reach later keeps 0 inside its box."""
    kc = k.reshape(k.shape[0] // chunk, chunk, *k.shape[1:])
    return kc.max(1), kc.min(1)


def _conf_items(conf: Dict[str, Any]):
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "tie_word_embeddings",
            "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
            "n_routed_experts_published", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "n_shared_experts")
    return tuple((k, conf[k]) for k in keep if conf.get(k) is not None)


def padded_len(n: int) -> int:
    """Row length the reference runs at: a whole number of query blocks
    (one compiled program per length)."""
    return -(-n // Q_BLOCK) * Q_BLOCK


# ---------------------------------------------------------------------------
# LeoAM's chunk choice
# ---------------------------------------------------------------------------

def tree_select(chunk_scores: np.ndarray, length: int, budget: int,
                chunk: int) -> List[int]:
    """Branch-and-bound top selection over ``length`` tokens whose score
    is their chunk's: pop the best segment; take it whole if it fits the
    tokens left in the budget, else split it in halves (ties: the lower
    position first).  Returns the chunks any taken segment lies in."""
    n = int(length)
    budget = min(budget, n)
    heap: List[Tuple[float, int, int]] = []
    for c in range(math.ceil(n / chunk)):
        heapq.heappush(heap, (-float(chunk_scores[c]), c * chunk,
                              min((c + 1) * chunk, n)))
    taken = 0
    sel = set()
    while taken < budget and heap:
        nub, lo, hi = heapq.heappop(heap)
        if hi - lo <= budget - taken:
            taken += hi - lo
            sel.add(lo // chunk)
            continue
        mid = lo + (hi - lo) // 2
        heapq.heappush(heap, (nub, lo, mid))
        heapq.heappush(heap, (nub, mid, hi))
    return sorted(sel)


class Chooser:
    """One sequence's chunk choice over all layers: each layer's min/max
    boxes, and the decayed use count of every chunk."""

    def __init__(self, rules: Dict[str, Any], n_table: int,
                 kmax: List[np.ndarray], kmin: List[np.ndarray]):
        self.r = rules
        self.chunk = int(rules["chunk_size"])
        self.kmax, self.kmin = kmax, kmin
        self.uses = np.zeros(n_table, np.float64)

    def choose(self, layer: int, q: np.ndarray, length: int) -> List[int]:
        """Chunks layer ``layer``'s selection query q reads over a cache
        of ``length`` tokens: (H, hd) scaled by 1/sqrt(hd) against the
        key boxes, or for MLA the absorbed query (H, r + rope) against the
        latent boxes (one box a chunk for all heads)."""
        r, chunk = self.r, self.chunk
        nv = -(-length // chunk)
        km, kn = self.kmax[layer][:nv], self.kmin[layer][:nv]
        Hk = km.shape[1]
        qg = q.astype(np.float64).reshape(Hk, -1, q.shape[-1])
        ub = (np.einsum("kgd,ckd->kc", np.maximum(qg, 0), km)
              + np.einsum("kgd,ckd->kc", np.minimum(qg, 0), kn))
        rate = (r["early_rate"] if layer < r["early_layers"]
                else r["importance_rate"])
        budget = max(chunk, int(math.ceil(length * rate)))
        sel = set(tree_select(ub.max(0) / chunk, length, budget, chunk))
        sel.update(range(int(r["sink_chunks"])))
        sel.update(range(max(0, nv - int(r["recent_chunks"])), nv))
        n_hot = max(1, int(len(self.uses) * float(r["hot_frac"])))
        sel.update(int(c) for c in np.argsort(-self.uses)[:n_hot] if c < nv)
        chosen = sorted(sel)
        self.uses *= float(r["hot_decay"])
        np.add.at(self.uses, np.asarray(chosen, np.int64), 1.0)
        return chosen

    def append(self, layer: int, pos: int, k: np.ndarray) -> None:
        c = pos // self.chunk
        self.kmax[layer][c] = np.maximum(self.kmax[layer][c], k)
        self.kmin[layer][c] = np.minimum(self.kmin[layer][c], k)


def served_logits(conf: Dict[str, Any], params: Any, prompt: Sequence[int],
                  served: Sequence[int], max_len: int, mode: str = "f32"
                  ) -> np.ndarray:
    """Logits (len(served), vocab) before each served token: the first
    from the dense pass over the prompt, every later one from the sparse
    decode step that reads the token before it."""
    rules = conf["leoam"]
    chunk = int(rules["chunk_size"])
    items = _conf_items(conf)
    n_layers = conf["num_hidden_layers"]
    P, n = len(prompt), len(served)
    L = padded_len(P + n)
    ids = np.zeros(L, np.int32)
    ids[:P] = np.asarray(prompt, np.int64)
    x = jnp.take(params["embed"], jnp.asarray(ids), axis=0).astype(F32)
    pos = jnp.arange(L, dtype=jnp.int32)
    moe = [flops.moe_layer(conf, i) for i in range(n_layers)]
    kcs, vcs, kmax, kmin = [], [], [], []
    for i in range(n_layers):
        x, k, v, lat = _prefill_layer(W.layer(params, i), x, pos, P,
                                      conf_items=items, mode=mode,
                                      moe=moe[i])
        kcs.append(k)
        vcs.append(v)
        hi, lo = _boxes(k if lat is None else lat, chunk=chunk)
        kmax.append(np.asarray(hi, np.float64))
        kmin.append(np.asarray(lo, np.float64))
    out = np.zeros((n, conf["vocab_size"]), np.float32)
    out[0] = np.asarray(_head(params, x[P - 1:P], conf_items=items,
                              mode=mode))[0]
    del x
    chooser = Chooser(rules, max_len // chunk, kmax, kmin)
    rows = np.arange(L)
    for t in range(1, n):
        p = P + t - 1
        x = jnp.take(params["embed"], jnp.asarray([served[t - 1]]),
                     axis=0).astype(F32)
        for i in range(n_layers):
            w = W.layer(params, i)
            q, k, v, lat, sel = _step_qkv(w, x, jnp.int32(p),
                                          conf_items=items, mode=mode)
            qs = (np.asarray(q) / math.sqrt(q.shape[-1]) if sel is None
                  else np.asarray(sel))
            chosen = chooser.choose(i, qs, p)
            pick = np.zeros(L // chunk, bool)
            pick[chosen] = True
            mask = (np.repeat(pick, chunk) & (rows < p)) | (rows == p)
            x, kcs[i], vcs[i] = _step_rest(
                w, x, q, k, v, kcs[i], vcs[i], jnp.int32(p),
                jnp.asarray(mask), conf_items=items, mode=mode, moe=moe[i])
            chooser.append(i, p, np.asarray(k if lat is None else lat,
                                            np.float64))
        out[t] = np.asarray(_head(params, x, conf_items=items, mode=mode))[0]
    return out
