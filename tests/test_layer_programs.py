"""The decode round's compiled dense layer work (``engine._layer_fn``):
the pre-attention program (norm, Q/K/V or the absorbed MLA query and
latent row, rotary) and the post-attention program (residual add, dense
or MoE MLP) equal the eager composition of the same functions on
``a[r]``-sliced weights, and compile once per (period position or
prologue index, batch size), never per layer or per round."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models import lm
from repro.models.common import rms_norm, rotate
from repro.serving.engine import BatchedLeoAMEngine, EngineCfg

B = 3
# the round's phases that run the layer programs or pick their weights
LAYER_PHASES = {"leoam.qkv", "leoam.mlp", "leoam.weights"}
# GQA without and with qk_norm, absorbed MLA with MoE MLPs
CONFIGS = ("phi4-mini-3.8b", "qwen3-1.7b", "deepseek-v2-lite-16b")


@pytest.fixture(scope="module", params=CONFIGS)
def model(request):
    cfg = get_config(request.param, smoke=True)
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    params = lm.init(cfg, jax.random.PRNGKey(3))
    return cfg, params


def _eager_pre(cfg, blk, h, pos):
    hln = rms_norm(h, blk["ln1"], cfg.norm_eps)
    p = blk["core"]
    if cfg.mla is None:
        q, k, v = attn._qkv(p, cfg, hln, pos)
        return {"q": q, "q_sel": q[:, 0], "k_new": k, "v_new": v}
    m = cfg.mla
    q_nope, q_rope = attn._mla_q(p, cfg, hln, pos)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_lat = jnp.einsum("bhd,hrd->bhr", q_nope[:, 0], p["wk_b"]) * scale
    q_rope = q_rope[:, 0] * scale
    kv_a = (hln @ p["wkv_a"])[:, 0]
    ckv = rms_norm(kv_a[:, :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope = rotate(cfg, kv_a[:, None, None, m.kv_lora_rank:], pos)[:, 0, 0]
    return {"q_lat": q_lat, "q_rope": q_rope,
            "q_sel": jnp.concatenate([q_lat, q_rope], -1),
            "lat_new": jnp.concatenate([ckv, krope], -1)}


def _close(got, want):
    # bf16 keeps 8 bits of mantissa: one rounding step apart at most
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2 ** -6, atol=2 ** -6)


@pytest.mark.parametrize("layer", ["prologue", "first repeat", "last repeat"])
def test_layer_programs_are_the_eager_math(model, layer):
    cfg, params = model
    prologue, period, repeats = lm._layer_plan(cfg)
    eng = BatchedLeoAMEngine(cfg, params, EngineCfg(max_len=64), max_seqs=B)
    if layer == "prologue":
        assert prologue[0][1].startswith("attn")
        where, pi, mlpk = "prologue", 0, prologue[0][2]
        w = blk = params["prologue"][0]
        r = None
    else:
        rep = 0 if layer == "first repeat" else repeats - 1
        where, pi, (kind, mlpk) = "body", 0, period[0]
        assert kind.startswith("attn")
        w = params["body"][0]
        blk = jax.tree.map(lambda a: a[rep], w)
        r = eng._repeat_idx[rep]
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(B, 1, cfg.d_model), cfg.dtype)
    pos = jnp.asarray([[17], [40], [63]], jnp.int32)

    got = eng._layer_program("pre", where, pi, mlpk)(w, r, h, pos)
    want = _eager_pre(cfg, blk, h, pos)
    for k, v in want.items():
        assert got[k].shape == v.shape and got[k].dtype == v.dtype, k
        _close(got[k], v)
    np.testing.assert_array_equal(got["wo"], blk["core"]["wo"])

    y = jnp.asarray(rng.randn(B, 1, cfg.d_model), cfg.dtype)
    got = eng._layer_program("post", where, pi, mlpk)(w, r, h, y)
    want, _ = lm._apply_mlp(blk, cfg, mlpk, h + y, None, no_drop=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    _close(got, want)
    eng.store.close()


def test_layer_programs_compile_once_per_position_and_batch(rng):
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    # every chunk selected and a pool that holds them all: after the first
    # round no tier fetch uploads, so only the layer programs could compile
    cfg = dataclasses.replace(
        cfg, leoam=dataclasses.replace(cfg.leoam, chunk_size=16,
                                       importance_rate=1.0, early_rate=1.0,
                                       min_seq_for_sparse=32))
    prologue, period, repeats = lm._layer_plan(cfg)
    assert repeats >= 2
    params = lm.init(cfg, jax.random.PRNGKey(1))
    eng = BatchedLeoAMEngine(cfg, params, EngineCfg(max_len=128),
                             max_seqs=B, device_chunk_budget=16)
    toks = {}
    for L in (40, 41, 42):
        sid, tok = eng.add_sequence(rng.randint(2, cfg.vocab_size, L))
        toks[sid] = tok
    for _ in range(4):
        toks = eng.decode_round(toks)
    first, *rest = eng.round_profiles
    assert first["compiles_by_phase"].get("leoam.qkv", 0) >= 1
    assert first["compiles_by_phase"].get("leoam.mlp", 0) >= 1
    for prof in rest:
        assert not set(prof["compiles_by_phase"]) & LAYER_PHASES, prof
    # the second round's one compile is the pool's first fold of appended
    # rows (``leoam.fetch``); from the third on nothing compiles
    assert set(rest[0]["compiles_by_phase"]) <= {"leoam.fetch"}
    for prof in rest[1:]:
        assert prof["compiles"] == 0, prof["compiles_by_phase"]
    # one program a (pre|post, position): prologue layers and the one
    # body position, not one per repeat ...
    n_pos = len(prologue) + len(period)
    assert sorted(eng._layer_programs) == sorted(
        [(w, "prologue", i) for w in ("pre", "post")
         for i in range(len(prologue))]
        + [(w, "body", i) for w in ("pre", "post")
           for i in range(len(period))])
    assert len(eng._layer_programs) == 2 * n_pos
    assert all(fn._cache_size() == 1 for fn in eng._layer_programs.values())
    # ... and one compile of each per batch size
    eng.release(sorted(toks)[0])
    toks = dict(sorted(toks.items())[1:])
    for _ in range(2):
        toks = eng.decode_round(toks)
    assert all(fn._cache_size() == 2 for fn in eng._layer_programs.values())
    assert not (set(eng.round_profiles[-1]["compiles_by_phase"])
                & LAYER_PHASES)
    eng.store.close()
