"""Pallas TPU kernel: fused KV transit decompression (paper §4.4).

KV chunks arrive from the host tier int4/int8-packed (the DTP codec); this
kernel unpacks + rescales them on-chip so the decompression cost t(Dθ) the
paper's θ-balance trades against never touches HBM bandwidth twice — the
packed bytes are read once, the rescaled rows land directly in VMEM.

Grid: one program per KV chunk.  Block layout follows the TPU tiling
rule (last two block dims (8, 128)-aligned or spanning the array): the
per-channel scale rides as ``(N, 1, d)`` so its block is ``(1, d)`` over a
unit axis.  The int4 payload interleaves channels (byte i holds channels
2i and 2i+1), a lane shuffle Mosaic has no relayout for; the kernel
scatters the two nibble planes into place with 0/1 selection matrices on
the MXU instead — small integers times 0/1 summed once, so the result is
exact and bitwise equal to the jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# output dtypes the kernel stores directly; others (the store's fp16) are
# stored as f32 and cast outside — a single rounding either way
_KERNEL_OUT = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _dequant_int8_kernel(d_ref, s_ref, o_ref):
    d = d_ref[0].astype(jnp.float32)                    # (c, d)
    s = s_ref[0].astype(jnp.float32)                    # (1, d)
    o_ref[0] = (d * s).astype(o_ref.dtype)


def _dequant_int4_kernel(d_ref, s_ref, ev_ref, od_ref, o_ref):
    u = d_ref[0].astype(jnp.int32) & 0xFF               # (c, d//2)
    lo = u & 0xF
    hi = (u >> 4) & 0xF
    lo = jnp.where(lo > 7, lo - 16, lo).astype(jnp.bfloat16)
    hi = jnp.where(hi > 7, hi - 16, hi).astype(jnp.bfloat16)
    # q[:, 2i] = lo[:, i], q[:, 2i+1] = hi[:, i]: values in [-8, 7] are
    # exact in bf16 and each output lane sums exactly one nonzero product
    q = (jnp.dot(lo, ev_ref[...], preferred_element_type=jnp.float32)
         + jnp.dot(hi, od_ref[...], preferred_element_type=jnp.float32))
    s = s_ref[0].astype(jnp.float32)                    # (1, d)
    o_ref[0] = (q * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("codec", "out_dtype", "interpret"))
def kv_dequant_pallas(data: jax.Array, scale: jax.Array, *, codec: str,
                      out_dtype=jnp.bfloat16, interpret: bool = False
                      ) -> jax.Array:
    """data: (N, c, dp) int8 (dp = d or d//2); scale: (N, d) f32."""
    N, c, dp = data.shape
    d = scale.shape[-1]
    kdtype = jnp.dtype(out_dtype) if jnp.dtype(out_dtype) in _KERNEL_OUT \
        else jnp.dtype(jnp.float32)
    in_specs = [pl.BlockSpec((1, c, dp), lambda n: (n, 0, 0)),
                pl.BlockSpec((1, 1, d), lambda n: (n, 0, 0))]
    args = [data, scale.reshape(N, 1, d)]
    kern = _dequant_int8_kernel
    if codec == "int4":
        kern = _dequant_int4_kernel
        lane = jnp.arange(d, dtype=jnp.int32)[None, :]
        pair = 2 * jnp.arange(dp, dtype=jnp.int32)[:, None]
        args += [(lane == pair).astype(jnp.bfloat16),
                 (lane == pair + 1).astype(jnp.bfloat16)]
        in_specs += [pl.BlockSpec((dp, d), lambda n: (0, 0))] * 2
    out = pl.pallas_call(
        kern,
        grid=(N,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, c, d), lambda n: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, c, d), kdtype),
        interpret=interpret,
    )(*args)
    return out.astype(out_dtype)
