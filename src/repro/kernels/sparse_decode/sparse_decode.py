"""Pallas TPU kernel: LeoAM sparse decode attention.

The selected chunk ids are a **scalar-prefetch** operand: the BlockSpec
index_map reads ``ids[b, h, j]`` to DMA exactly the selected KV chunks
HBM→VMEM — the gather never materializes in HBM.  Flash accumulators
(num/den/m) live in VMEM scratch across the sequential ``nsel`` grid dim;
invalid tail tokens (beyond ``length``) are masked with -inf.

Grid: (B, Hkv, nsel) — (parallel, parallel, arbitrary).
Block shapes: q (G, hd) resident per (b, h); KV chunks (chunk, hd), chunk a
multiple of the 128 MXU lanes for the score matmul.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = float("-inf")


def _decode_kernel(ids_ref, len_ref, q_ref, k_ref, v_ref,
                   num_ref, den_ref, m_ref,
                   acc, den_s, m_s, *, chunk: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    nsel = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        den_s[...] = jnp.zeros_like(den_s)
        m_s[...] = jnp.full_like(m_s, NEG)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
    kc = k_ref[0, :, 0].astype(jnp.float32)              # (chunk, hd)
    vc = v_ref[0, :, 0].astype(jnp.float32)

    cid = ids_ref[b, h, j]
    pos = cid * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    valid = pos < len_ref[0]                             # (1, chunk)

    s = jnp.dot(q, kc.T, preferred_element_type=jnp.float32)  # (G, chunk)
    s = jnp.where(valid, s, NEG)

    m_prev = m_s[...]                                    # (G, 128) lane-pad
    m_cur = jnp.max(s, axis=-1, keepdims=True)           # (G, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    scale = jnp.where(jnp.isfinite(m_prev),
                      jnp.exp(m_prev - m_safe), 0.0)     # (G, 1)
    e = jnp.where(valid, jnp.exp(s - m_safe), 0.0)       # (G, chunk)
    acc[...] = acc[...] * scale[:, :1] + jnp.dot(
        e, vc, preferred_element_type=jnp.float32)
    den_s[...] = den_s[...] * scale + jnp.sum(e, axis=-1, keepdims=True)
    m_s[...] = m_new

    @pl.when(j == nsel - 1)
    def _out():
        num_ref[0, 0] = acc[...]
        den_ref[0, 0] = den_s[:, 0]
        m_ref[0, 0] = m_s[:, 0]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def sparse_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                         ids: jax.Array, length: jax.Array, *, chunk: int,
                         interpret: bool = False
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q: (B,Hkv,G,hd) scaled; k/v: (B,S,Hkv,hd); ids: (B,Hkv,nsel) int32;
    length: () int32 -> (num, den, m) partial-softmax triple."""
    B, Hkv, G, hd = q.shape
    S = k.shape[1]
    nsel = ids.shape[-1]
    assert S % chunk == 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, nsel),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, ids, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, chunk, 1, hd),
                         lambda b, h, j, ids, ln: (b, ids[b, h, j], h, 0)),
            pl.BlockSpec((1, chunk, 1, hd),
                         lambda b, h, j, ids, ln: (b, ids[b, h, j], h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, j, ids, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, G), lambda b, h, j, ids, ln: (b, h, 0)),
            pl.BlockSpec((1, 1, G), lambda b, h, j, ids, ln: (b, h, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((B, Hkv, G, hd), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
        jax.ShapeDtypeStruct((B, Hkv, G), jnp.float32),
    ]
    kernel = functools.partial(_decode_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ids, length.reshape(1), q, k, v)
