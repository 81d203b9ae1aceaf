"""Pallas TPU kernels: online product-quantization k-means (PQ abstracts).

Two kernels over per-subspace key vectors ``x: (m, N, dsub)`` (head_dim
split into ``m`` subvectors of ``dsub`` lanes) and a codebook
``cb: (m, K, dsub)``:

* **assign** — nearest-centroid codes.  Per grid step (subspace i, row
  tile n) the kernel holds one (TN, dsub) vector tile and the subspace's
  (K, dsub) codebook in VMEM and issues one MXU matmul:
  ``argmin_k |x - c_k|^2 == argmin_k (|c_k|^2 - 2 x.c_k)`` — the |x|^2
  term is constant per row, so the full distance never materializes.
* **update** — one k-means accumulation pass: per-centroid coordinate
  sums and member counts via a one-hot matmul, accumulated across row
  tiles (grid dim 1 revisits the same output block, the TPU-sequential
  reduction pattern).

Every block's last two dims are (8, 128)-aligned or span the array (the
TPU tiling rule): codes and counts travel with a unit middle axis,
``(m, 1, N)`` and ``(m, 1, K)``, so their blocks are lane-dense rows.

Both run in interpret mode on CPU (how the tier-1 suite verifies them);
the jnp oracle in ``ref.py`` uses the SAME distance expression so argmin
tie-breaking matches bit-for-bit.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, cb_ref, codes_ref):
    x = x_ref[0].astype(jnp.float32)                    # (TN, dsub)
    cb = cb_ref[0].astype(jnp.float32)                  # (K, dsub)
    # (K, dsub) x (TN, dsub)^T on the MXU -> (K, TN): rows lie along the
    # lanes, so the argmin over centroids is a sublane reduction and the
    # codes come out lane-dense; |c_k|^2 folded in afterwards
    xc = jax.lax.dot_general(cb, x, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    d = jnp.sum(cb * cb, axis=1)[:, None] - 2.0 * xc
    # first minimal index (argmin's tie-break) without a variadic reduce
    K = d.shape[0]
    k = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
    hit = d == jnp.min(d, axis=0, keepdims=True)
    codes_ref[0] = jnp.min(jnp.where(hit, k, K), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def pq_assign_pallas(x: jax.Array, cb: jax.Array, *, tile_n: int = 256,
                     interpret: bool = False) -> jax.Array:
    """x: (m, N, dsub); cb: (m, K, dsub) -> codes (m, N) int32.

    N is padded to a multiple of ``tile_n`` (itself a multiple of 128) by
    the caller (ops.py); codes travel as ``(m, 1, N)`` so each block is a
    lane-dense ``(1, tile_n)`` row over a unit axis.
    """
    m, N, dsub = x.shape
    K = cb.shape[1]
    assert N % tile_n == 0, (N, tile_n)
    grid = (m, N // tile_n)
    codes = pl.pallas_call(
        _assign_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_n, dsub), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, K, dsub), lambda i, n: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_n), lambda i, n: (i, 0, n)),
        out_shape=jax.ShapeDtypeStruct((m, 1, N), jnp.int32),
        interpret=interpret,
    )(x, cb)
    return codes.reshape(m, N)


def _update_kernel(x_ref, codes_ref, sums_ref, counts_ref):
    # grid dim 1 revisits the same (subspace-indexed) output block: zero
    # it on the first tile, accumulate on every tile
    @pl.when(pl.program_id(1) == 0)
    def _init():
        sums_ref[0] = jnp.zeros_like(sums_ref[0])
        counts_ref[0] = jnp.zeros_like(counts_ref[0])

    x = x_ref[0].astype(jnp.float32)                    # (TN, dsub)
    TN = x.shape[0]
    K = sums_ref.shape[1]
    # codes arrive lane-dense (1, TN); the one-hot wants them down the
    # sublanes: an (8, TN) -> (TN, 8) transpose is a native relayout
    codes = jnp.broadcast_to(codes_ref[0], (8, TN)).T[:, :1]   # (TN, 1)
    # padded rows carry code == K (out of range): the one-hot row is all
    # zeros, so padding never perturbs sums or counts
    onehot = (codes == jax.lax.broadcasted_iota(
        jnp.int32, (TN, K), 1)).astype(jnp.float32)
    sums_ref[0] += jnp.dot(onehot.T, x, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    counts_ref[0] += jnp.sum(onehot, axis=0, keepdims=True)   # (1, K)


@functools.partial(jax.jit,
                   static_argnames=("n_centroids", "tile_n", "interpret"))
def pq_update_pallas(x: jax.Array, codes: jax.Array, *, n_centroids: int,
                     tile_n: int = 256, interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """x: (m, N, dsub); codes: (m, N) int32 -> (sums (m, K, dsub),
    counts (m, K)) — one accumulation pass of Lloyd's update.  Codes and
    counts ride with a unit middle axis (lane-dense blocks)."""
    m, N, dsub = x.shape
    assert N % tile_n == 0, (N, tile_n)
    grid = (m, N // tile_n)
    out_shape = [
        jax.ShapeDtypeStruct((m, n_centroids, dsub), jnp.float32),
        jax.ShapeDtypeStruct((m, 1, n_centroids), jnp.float32),
    ]
    sums, counts = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_n, dsub), lambda i, n: (i, n, 0)),
            pl.BlockSpec((1, 1, tile_n), lambda i, n: (i, 0, n)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_centroids, dsub), lambda i, n: (i, 0, 0)),
            pl.BlockSpec((1, 1, n_centroids), lambda i, n: (i, 0, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(x, codes.reshape(m, 1, N))
    return sums, counts.reshape(m, n_centroids)
