"""PQ abstract plane: dispatching kernel wrappers + store-facing helpers.

``pq_assign`` / ``pq_update`` dispatch like every kernel in this tree
(Pallas on TPU, interpret for validation, jnp reference otherwise).  On
top of them:

* :func:`pq_train` — deterministic online mini-batch k-means.  An
  untrained codebook initializes from strided batch rows and runs a few
  Lloyd iterations; a trained one takes a single running-mean merge
  (``c_k <- (c_k * n_k + sum_batch_k) / (n_k + cnt_batch_k)``), so
  per-layer codebooks keep adapting as new sequences ingest.  No RNG
  anywhere: two runs over the same ingest order produce byte-identical
  codebooks.
* :func:`pq_encode` / :func:`pq_decode` — uint8 codes per (token, kv
  head) key vector; decode is the centroid gather (the quantities the
  round-trip property tests bound).
* :func:`adc_chunk_scores` — the engine's asymmetric-distance path: one
  (B, Hkv, m, K) lookup table per round/layer (q·centroid dots), then a
  code gather + subspace sum + per-chunk max.  Replaces the min/max
  bounds matmul for chunks whose codes are fresh.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.pq.pq_kmeans import pq_assign_pallas, pq_update_pallas
from repro.kernels.pq.ref import pq_assign_ref, pq_update_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _row_tile(N: int, tile_n: int) -> int:
    """Row tile of the Pallas grid: a multiple of 128 (codes ride along
    the lanes), no larger than ``N`` rounded up to one."""
    if tile_n % 128:
        raise ValueError(f"tile_n={tile_n} must be a multiple of 128")
    return min(tile_n, -(-N // 128) * 128)


def pq_assign(x: jax.Array, cb: jax.Array, *, impl: Optional[str] = None,
              tile_n: int = 256) -> jax.Array:
    """x: (m, N, dsub); cb: (m, K, dsub) -> codes (m, N) int32.

    impl: None (auto) | "pallas" | "interpret" | "ref".
    """
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return pq_assign_ref(x, cb)
    N = x.shape[1]
    tile = _row_tile(N, tile_n)
    pad = (-N) % tile
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], pad, x.shape[2]), x.dtype)], axis=1)
    codes = pq_assign_pallas(x, cb, tile_n=tile,
                             interpret=(impl == "interpret"))
    return codes[:, :N] if pad else codes


def pq_update(x: jax.Array, codes: jax.Array, n_centroids: int, *,
              impl: Optional[str] = None, tile_n: int = 256
              ) -> Tuple[jax.Array, jax.Array]:
    """One Lloyd accumulation: (sums (m, K, dsub), counts (m, K))."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return pq_update_ref(x, codes, n_centroids)
    N = x.shape[1]
    tile = _row_tile(N, tile_n)
    pad = (-N) % tile
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((x.shape[0], pad, x.shape[2]), x.dtype)], axis=1)
        # padded rows carry the out-of-range sentinel K: all-zero one-hot
        codes = jnp.concatenate(
            [codes, jnp.full((codes.shape[0], pad), n_centroids,
                             codes.dtype)], axis=1)
    return pq_update_pallas(x, codes, n_centroids=n_centroids, tile_n=tile,
                            interpret=(impl == "interpret"))


def _subspaces(vecs: np.ndarray, m: int) -> np.ndarray:
    """(n, d) vectors -> (m, n, dsub) per-subspace rows (f32)."""
    n, d = vecs.shape
    return np.ascontiguousarray(
        vecs.reshape(n, m, d // m).transpose(1, 0, 2)).astype(np.float32)


def pq_train(vecs: np.ndarray, codebook: np.ndarray, counts: np.ndarray, *,
             iters: int = 4, impl: Optional[str] = None
             ) -> Tuple[np.ndarray, np.ndarray]:
    """Online k-means step over one ingest batch.

    vecs: (n, d) raw key vectors; codebook: (m, K, dsub); counts: (m, K)
    running member counts (all-zero == untrained).  Returns the updated
    (codebook, counts) — numpy, ready for the store's RAM mirror.
    """
    cb = np.asarray(codebook, np.float32).copy()
    cnt = np.asarray(counts, np.float64).copy()
    m, K, _dsub = cb.shape
    n = int(vecs.shape[0])
    if n == 0:
        return cb, cnt
    x = _subspaces(np.asarray(vecs, np.float32), m)       # (m, n, dsub)
    xj = jnp.asarray(x)
    if cnt.sum() == 0:
        # deterministic strided-row init (no RNG); n < K duplicates rows,
        # leaving some clusters empty — they keep their seed value
        idx = (np.arange(K) * max(1, n // K)) % n
        cb = x[:, idx].copy()
        c = np.zeros((m, K), np.float64)
        for _ in range(max(1, iters)):
            codes = pq_assign(xj, jnp.asarray(cb), impl=impl)
            sums, cf = pq_update(xj, codes, K, impl=impl)
            sums, c = np.asarray(sums, np.float64), np.asarray(cf, np.float64)
            nz = c > 0
            cb[nz] = (sums[nz] / c[nz][:, None]).astype(np.float32)
        cnt = c
    else:
        codes = pq_assign(xj, jnp.asarray(cb), impl=impl)
        sums, cf = pq_update(xj, codes, K, impl=impl)
        sums, c = np.asarray(sums, np.float64), np.asarray(cf, np.float64)
        tot = cnt + c
        nz = tot > 0
        merged = (cb.astype(np.float64) * cnt[..., None] + sums)
        cb[nz] = (merged[nz] / tot[nz][:, None]).astype(np.float32)
        cnt = tot
    return cb, cnt


def pq_encode(vecs: np.ndarray, codebook: np.ndarray, *,
              impl: Optional[str] = None) -> np.ndarray:
    """(n, d) key vectors -> (n, m) uint8 nearest-centroid codes."""
    cb = np.asarray(codebook, np.float32)
    m, K, _dsub = cb.shape
    assert K <= 256, K
    x = _subspaces(np.asarray(vecs, np.float32), m)
    codes = np.asarray(pq_assign(jnp.asarray(x), jnp.asarray(cb), impl=impl))
    return np.ascontiguousarray(codes.T).astype(np.uint8)


def pq_decode(codes: np.ndarray, codebook: np.ndarray) -> np.ndarray:
    """(..., m) uint8 codes -> (..., d) reconstructed vectors (f32)."""
    cb = np.asarray(codebook, np.float32)
    m, _K, dsub = cb.shape
    flat = np.asarray(codes).reshape(-1, m).astype(np.int64)
    out = cb[np.arange(m)[None, :], flat]                 # (N, m, dsub)
    return out.reshape(np.asarray(codes).shape[:-1] + (m * dsub,))


@jax.jit
def _adc_scores_jit(q_sum: jax.Array, cb: jax.Array, codes: jax.Array,
                    lengths: jax.Array) -> jax.Array:
    B, Hkv, hd = q_sum.shape
    m, _K, dsub = cb.shape
    nc, chunk = codes.shape[1], codes.shape[2]
    lut = jnp.einsum("bhmd,mkd->bhmk",
                     q_sum.reshape(B, Hkv, m, dsub), cb)  # (B,Hkv,m,K)
    idx = codes.astype(jnp.int32).transpose(0, 3, 4, 1, 2) \
        .reshape(B, Hkv, m, nc * chunk)
    vals = jnp.take_along_axis(lut, idx, axis=3)          # (B,Hkv,m,nc*chunk)
    tok = vals.sum(2).reshape(B, Hkv, nc, chunk)
    pos = jnp.arange(nc * chunk).reshape(nc, chunk)
    live = pos[None] < lengths[:, None, None]             # (B, nc, chunk)
    tok = jnp.where(live[:, None], tok, -jnp.inf)
    return tok.max(-1)                                    # (B, Hkv, nc)


def adc_chunk_scores(q_sum: np.ndarray, codebook: np.ndarray,
                     codes: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Asymmetric-distance chunk scores off PQ codes.

    q_sum: (B, Hkv, hd) group-summed pre-scaled queries (the exact-logit
    analog of the bounds path's per-group sum); codebook: (m, K, dsub);
    codes: (B, nc, chunk, Hkv, m) uint8; lengths: (B,) live token counts
    (tokens at or past a sequence's length are masked out of the max).
    Returns (B, Hkv, nc) f32 — same layout as the bounds matmul's ub.
    """
    return np.asarray(_adc_scores_jit(
        jnp.asarray(q_sum, jnp.float32), jnp.asarray(codebook, jnp.float32),
        jnp.asarray(codes), jnp.asarray(np.asarray(lengths), jnp.int32)))
