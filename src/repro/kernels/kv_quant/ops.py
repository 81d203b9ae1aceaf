"""Dispatching wrapper for KV transit decompression."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.kv_quant.kv_quant import kv_dequant_pallas
from repro.kernels.kv_quant.ref import dequant_int4_ref, dequant_int8_ref


def kv_dequant(data: jax.Array, scale: jax.Array, *, codec: str = "int4",
               out_dtype=jnp.bfloat16, impl: Optional[str] = None) -> jax.Array:
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        fn = dequant_int4_ref if codec == "int4" else dequant_int8_ref
        return fn(data, scale, out_dtype)
    # pad the chunk count to a power of two: the upload delta varies every
    # round, and each distinct grid would compile its own kernel
    N = data.shape[0]
    pad = (1 << max(0, N - 1).bit_length()) - N
    if pad:
        data = jnp.concatenate(
            [data, jnp.zeros((pad, *data.shape[1:]), data.dtype)])
        scale = jnp.concatenate(
            [scale, jnp.zeros((pad, *scale.shape[1:]), scale.dtype)])
    out = kv_dequant_pallas(data, scale, codec=codec, out_dtype=out_dtype,
                            interpret=(impl == "interpret"))
    return out[:N] if pad else out
