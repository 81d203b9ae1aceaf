"""jnp reference for the PQ k-means kernels.

Uses the SAME ``|c_k|^2 - 2 x.c_k`` distance expression as the Pallas
kernel so argmin tie-breaking (first minimal index) matches exactly —
the kernel tests compare codes with ``assert_array_equal``, not allclose.
Both sides ask for full f32 matmul precision: a TPU's default f32 dot
rounds its inputs to bf16, which would move near-tie codes.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def pq_assign_ref(x: jax.Array, cb: jax.Array) -> jax.Array:
    """x: (m, N, dsub); cb: (m, K, dsub) -> codes (m, N) int32."""
    x = jnp.asarray(x, jnp.float32)
    cb = jnp.asarray(cb, jnp.float32)
    d = jnp.sum(cb * cb, axis=-1)[:, None, :] \
        - 2.0 * jnp.einsum("mnd,mkd->mnk", x, cb,
                           precision=jax.lax.Precision.HIGHEST)
    return jnp.argmin(d, axis=-1).astype(jnp.int32)


def pq_update_ref(x: jax.Array, codes: jax.Array, n_centroids: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """x: (m, N, dsub); codes: (m, N) -> (sums (m, K, dsub), counts (m, K)).

    Out-of-range codes (the dispatcher's padding sentinel ``K``) match no
    centroid and contribute nothing, same as the kernel's one-hot.
    """
    x = jnp.asarray(x, jnp.float32)
    onehot = (jnp.asarray(codes, jnp.int32)[..., None]
              == jnp.arange(n_centroids)[None, None, :]).astype(jnp.float32)
    sums = jnp.einsum("mnk,mnd->mkd", onehot, x,
                      precision=jax.lax.Precision.HIGHEST)
    counts = jnp.sum(onehot, axis=1)
    return sums, counts
