"""Compile-only checks for a described TPU v5e: the served kernels and the
pooled attention dispatch at phi4-mini-3.8b widths go through the TPU
compiler without a chip attached.  Interpret mode cannot see what this
catches (block layouts Mosaic refuses, VMEM overruns).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file."""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.kv_quant.kv_quant import kv_dequant_pallas
from repro.kernels.pq.pq_kmeans import pq_assign_pallas, pq_update_pallas
from repro.models import lm
from repro.serving.engine import _attend_pooled, _layer_fn

# phi4-mini-3.8b: 8 kv heads x head_dim 128, store chunk 64, PQ m = 16
# subvectors of 8 lanes, K = 256 centroids; N = one layer's keys at
# max_len 4096
D, CHUNK, N_CHUNKS = 8 * 128, 64, 16
M, DSUB, K, N = 16, 8, 256, 4096 * 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off here
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("out_dtype", [jnp.float16, jnp.bfloat16])
@pytest.mark.parametrize("codec", ["int4", "int8"])
def test_kv_dequant_compiles_for_v5e(one_chip, codec, out_dtype):
    dp = D // 2 if codec == "int4" else D
    txt = _compiled_text(
        functools.partial(kv_dequant_pallas, codec=codec,
                          out_dtype=out_dtype),
        _spec(one_chip, (N_CHUNKS, CHUNK, dp), jnp.int8),
        _spec(one_chip, (N_CHUNKS, D), jnp.float32))
    assert "tpu_custom_call" in txt


def test_pq_assign_compiles_for_v5e(one_chip):
    txt = _compiled_text(
        functools.partial(pq_assign_pallas, tile_n=256),
        _spec(one_chip, (M, N, DSUB), jnp.float32),
        _spec(one_chip, (M, K, DSUB), jnp.float32))
    assert "tpu_custom_call" in txt


def test_pq_update_compiles_for_v5e(one_chip):
    txt = _compiled_text(
        functools.partial(pq_update_pallas, n_centroids=K, tile_n=256),
        _spec(one_chip, (M, N, DSUB), jnp.float32),
        _spec(one_chip, (M, N), jnp.int32))
    assert "tpu_custom_call" in txt


def test_attend_pooled_compiles_for_v5e(one_chip):
    """The served decode attention: B=4 sequences over a 512-slot fp16
    chunk pool (plus the scratch slot), 16 selected chunks each, phi4's
    24 query / 8 kv heads and bf16 output projection."""
    B, nmax, H, Hkv, hd, d_model = 4, 16, 24, 8, 128, 3072
    s = functools.partial(_spec, one_chip)
    lowered = jax.jit(functools.partial(_attend_pooled, attn_softcap=None)
                      ).lower(
        s((B, 1, H, hd), jnp.bfloat16),
        s((513, 2, CHUNK, Hkv, hd), jnp.float16),
        s((B, nmax), jnp.int32), s((B, nmax), jnp.int32), s((B,), jnp.int32),
        s((B, 1, Hkv, hd), jnp.bfloat16), s((B, 1, Hkv, hd), jnp.bfloat16),
        s((H * hd, d_model), jnp.bfloat16))
    compiled = lowered.compile()
    assert lowered.out_info.shape == (B, 1, d_model)
    # the gathered working set is 4 x 16 chunks: scratch stays far below
    # the slab it gathers from
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28


@pytest.mark.parametrize("which", ["pre", "post"])
def test_layer_programs_compile_for_v5e(one_chip, which):
    """The decode round's pre- and post-attention programs at
    phi4-mini-3.8b widths, B=4: the body's stacked weights (30 repeats
    after the 2 prologue layers: 32 layers) in and a traced layer index,
    sliced inside the program."""
    cfg = get_config("phi4-mini-3.8b")
    _, period, repeats = lm._layer_plan(cfg)
    assert len(lm._layer_plan(cfg)[0]) + repeats * len(period) == 32
    B, d = 4, cfg.d_model
    s = functools.partial(_spec, one_chip)
    w = jax.tree.map(lambda a: s(a.shape, a.dtype),
                     lm.abstract_params(cfg)["body"][0])
    h = s((B, 1, d), jnp.bfloat16)
    arg = s((B, 1), jnp.int32) if which == "pre" else h
    lowered = jax.jit(_layer_fn(cfg, which, True, period[0][1])).lower(
        w, s((), jnp.int32), h, arg)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    if which == "pre":
        out = lowered.out_info
        assert out["q"].shape == (B, 1, cfg.n_heads, cfg.hd)
        assert out["q_sel"].shape == (B, cfg.n_heads, cfg.hd)
        assert out["k_new"].shape == (B, 1, cfg.n_kv_heads, cfg.hd)
        assert out["wo"].shape == w["core"]["wo"].shape[1:]
    else:
        assert lowered.out_info.shape == (B, 1, d)
    # the slice feeds the products in place: no layer's weights are
    # copied to scratch (one layer's MLP alone is 151 MB)
    assert mem.temp_size_in_bytes < 2 ** 24
