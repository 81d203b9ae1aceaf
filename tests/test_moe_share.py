"""An expert layer that holds one share of an expert-parallel deployment
(``MoECfg.router_width``, ``first_expert``, ``norm_topk``;
``models/mlp.py:moe_apply``): the router scores every published expert,
the layer computes only the picks on the experts it holds, and the shares
of all the chips add up to the uncut layer.  With the defaults the layer
computes what it computed before shares existed, bit for bit, and a
served share keeps chunked admission token-identical to whole-prompt
admission."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoECfg
from repro.models import lm
from repro.models import mlp as mlp_mod

ROUTER, HELD, TOP_K = 16, 4, 4
T = 24


def _cfg(**moe):
    """DeepSeek-V2-Lite's small variant with one shared expert, its
    expert block replaced by ``moe``."""
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, **{"n_shared": 1, **moe}))


def _uncut(**kw):
    return _cfg(n_experts=ROUTER, top_k=TOP_K, **kw)


def _share(first, **kw):
    return _cfg(n_experts=HELD, top_k=TOP_K, router_width=ROUTER,
                first_expert=first, **kw)


def _weights(cfg, seed=0):
    """Random weights of an uncut expert block: router, experts, shared."""
    defs = mlp_mod.moe_params(cfg)
    keys = jax.random.split(jax.random.key(seed), len(defs))
    return {k: jax.random.normal(kk, d.shape, jnp.float32)
            * d.shape[-2] ** -0.5 for (k, d), kk in zip(sorted(defs.items()),
                                                         keys)}


def _held(p, first):
    """One chip's weights: the whole router, its experts' slices."""
    return {k: v[first:first + HELD] if k in ("w_gate", "w_up", "w_down")
            else v for k, v in p.items()}


def _x(seed=1, rows=T, d=None):
    d = d or _cfg().d_model
    return jax.random.normal(jax.random.key(seed), (1, rows, d), jnp.float32)


def _shared(p, cfg, x):
    sp = {k[len("shared_"):]: v for k, v in p.items()
          if k.startswith("shared_")}
    return mlp_mod.dense_apply(sp, cfg, x)


@pytest.mark.parametrize("norm_topk", [True, False])
def test_the_shares_sum_to_the_uncut_layer(norm_topk):
    whole = _uncut(norm_topk=norm_topk)
    p, x = _weights(whole), _x()
    want, aux_whole = mlp_mod.moe_apply(p, whole, x, no_drop=True)
    routed = jnp.zeros_like(want)
    for first in range(0, ROUTER, HELD):
        cfg = _share(first, norm_topk=norm_topk)
        y, aux = mlp_mod.moe_apply(_held(p, first), cfg, x, no_drop=True)
        routed = routed + (y - _shared(p, cfg, x))
        # the aux losses see the whole router on every chip
        for k in ("moe_lb_loss", "moe_z_loss"):
            np.testing.assert_allclose(aux[k], aux_whole[k], rtol=1e-6)
    np.testing.assert_allclose(routed + _shared(p, whole, x), want,
                               rtol=1e-5, atol=1e-5)


def _plain(p, cfg, x):
    """The textbook expert layer over this share, token by token:
    softmax over the router, top-k, renormalised only under
    ``norm_topk``, each pick on a held expert adding its weighted SwiGLU,
    the shared SwiGLU added once; and the dispatch counts."""
    m = cfg.moe
    xs = np.asarray(x[0], np.float64)
    logits = xs @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.asarray(_shared(p, cfg, x)[0], np.float64)
    picks = 0
    hit_experts = set()
    for t in range(xs.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:m.top_k]
        w = probs[t, top]
        if m.norm_topk:
            w = w / w.sum()
        for e, pe in zip(top, w):
            j = e - m.first_expert
            if not 0 <= j < m.n_experts:
                continue
            picks += 1
            hit_experts.add(j)
            g = xs[t] @ np.asarray(p["w_gate"][j], np.float64)
            u = xs[t] @ np.asarray(p["w_up"][j], np.float64)
            out[t] += pe * ((g / (1 + np.exp(-g)) * u)
                            @ np.asarray(p["w_down"][j], np.float64))
    return out, (picks, m.n_experts * xs.shape[0], len(hit_experts))


@pytest.mark.parametrize("first", [0, 8])
@pytest.mark.parametrize("norm_topk", [False, True])
def test_a_share_is_the_plain_formula(first, norm_topk):
    cfg = _share(first, norm_topk=norm_topk)
    p = _held(_weights(_uncut()), first)
    x = _x(seed=2)
    y, _, n = mlp_mod.moe_apply(p, cfg, x, no_drop=True, counts=True)
    want, counts = _plain(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y[0], np.float64), want,
                               rtol=1e-4, atol=1e-5)
    assert tuple(int(v) for v in n) == counts
    assert counts[0] > 0


def _moe_apply_before(p, cfg, x, *, no_drop=False):
    """``moe_apply`` as it was before expert shares: the router over the
    held experts, the top-k weights always renormalised."""
    import math
    m = cfg.moe
    B, S, d = x.shape
    T_ = B * S
    E, K = m.n_experts, m.top_k
    xf = x.reshape(T_, d)
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    C = T_ if no_drop else max(4, int(math.ceil(
        T_ * m.top_k / m.n_experts * m.capacity_factor)))
    e_flat = top_e.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank = jnp.arange(T_ * K) - starts[sorted_e]
    keep = rank < C
    slot = jnp.where(keep, sorted_e * C + rank, E * C)
    token_of = order // K
    xe = jnp.zeros((E * C + 1, d), xf.dtype).at[slot].set(xf[token_of])
    xe = xe[:-1].reshape(E, C, d)
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_up"])
    g = jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    h = jax.nn.silu(g) * h
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * C, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], 0)
    slot_tk = jnp.zeros((T_ * K,), jnp.int32).at[order].set(
        slot.astype(jnp.int32))
    y_tk = ye[slot_tk].reshape(T_, K, d)
    y = jnp.einsum("tkd,tk->td", y_tk.astype(jnp.float32),
                   top_p.astype(jnp.float32)).astype(x.dtype)
    y = y + _shared(p, cfg, xf)
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.float32)
    frac_tokens = jnp.mean(jnp.sum(onehot, 1), 0)
    frac_probs = jnp.mean(probs, 0)
    aux = {"moe_lb_loss": E * jnp.sum(frac_tokens * frac_probs),
           "moe_z_loss": jnp.mean(jnp.square(
               jax.nn.logsumexp(logits, -1))),
           "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return y.reshape(B, S, d), aux


@pytest.mark.parametrize("no_drop", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_defaults_compute_what_they_did_bit_for_bit(no_drop, dtype):
    cfg = _cfg()
    assert (cfg.moe.router_width, cfg.moe.first_expert,
            cfg.moe.norm_topk) == (None, 0, True)
    p = {k: v if k == "router" else v.astype(dtype)
         for k, v in _weights(cfg, seed=3).items()}
    x = _x(seed=4, rows=40).astype(dtype)
    got = jax.jit(lambda p, x: mlp_mod.moe_apply(p, cfg, x,
                                                 no_drop=no_drop))(p, x)
    want = jax.jit(lambda p, x: _moe_apply_before(p, cfg, x,
                                                  no_drop=no_drop))(p, x)
    np.testing.assert_array_equal(np.asarray(got[0], np.float32),
                                  np.asarray(want[0], np.float32))
    assert set(got[1]) == set(want[1])
    for k in want[1]:
        np.testing.assert_array_equal(got[1][k], want[1][k])


def test_a_share_that_does_not_fit_the_router_is_refused():
    with pytest.raises(ValueError, match="not among the 16"):
        MoECfg(n_experts=4, top_k=2, d_ff_expert=8, router_width=16,
               first_expert=13)


def test_the_layout_routes_over_the_published_width():
    cfg = _share(8)
    layout = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    mlp = layout["body"][0]["mlp"]
    assert mlp["router"].shape[-2:] == (cfg.d_model, ROUTER)
    assert mlp["w_up"].shape[-3:] == (HELD, cfg.d_model, cfg.moe.d_ff_expert)
    # the analytic count holds the published router too
    narrow = _cfg(n_experts=HELD, top_k=TOP_K, router_width=HELD + 8,
                  first_expert=8)
    assert cfg.n_params() - narrow.n_params() == (ROUTER - HELD - 8) \
        * cfg.d_model * sum(m == "moe" for m in cfg.mlp_kinds())


# ---------------------------------------------------------------------------
# Served: chunked and whole admission with a share
# ---------------------------------------------------------------------------

_SERVED = {}


def _served():
    if not _SERVED:
        cfg = _share(4, norm_topk=False)
        cfg = dataclasses.replace(cfg, leoam=dataclasses.replace(
            cfg.leoam, chunk_size=16, importance_rate=0.4, early_rate=0.6,
            min_seq_for_sparse=32))
        _SERVED["cfg"] = cfg
        _SERVED["params"] = lm.init(cfg, jax.random.PRNGKey(5))
    return _SERVED["cfg"], _SERVED["params"]


def test_share_rows_are_independent_of_the_batch_shape(rng):
    cfg, params = _served()
    blk = jax.tree.map(lambda a: a[0], params["body"][0])
    x = jnp.asarray(rng.randn(1, 32, cfg.d_model).astype(np.float32))
    whole, _ = lm._apply_mlp(blk, cfg, "moe", x, None, no_drop=True)
    part, _ = lm._apply_mlp(blk, cfg, "moe", x[:, 8:16], None, no_drop=True)
    np.testing.assert_array_equal(np.asarray(whole[:, 8:16]),
                                  np.asarray(part))


@pytest.mark.parametrize("L", [41, 64])
def test_share_chunked_admission_matches_whole(L):
    from repro.serving.engine import BatchedLeoAMEngine, EngineCfg
    cfg, params = _served()
    prompt = np.random.RandomState(L).randint(2, cfg.vocab_size, L)

    def gen(chunked):
        eng = BatchedLeoAMEngine(
            cfg, params, EngineCfg(max_len=128, selection="tree",
                                   prefill_chunk_tokens=16), max_seqs=1)
        if chunked:
            sid, tok = eng.begin_admission(prompt).drain()
        else:
            sid, tok = eng.add_sequence(prompt)
        out, toks = [tok], {sid: tok}
        for _ in range(4):
            toks = eng.decode_round(toks)
            out.append(toks[sid])
        eng.store.close()
        return out

    assert gen(True) == gen(False)
