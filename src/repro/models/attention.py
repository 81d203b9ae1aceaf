"""Attention layers: GQA (with qk-norm / softcap / sliding window) and
DeepSeek MLA — train/prefill blocked-flash paths and LeoAM sparse decode.

The absorbed-MLA cache is ONE latent row per token (ckv ‖ krope); the
serving engine tiers exactly that row through its single-plane store and
scores chunks in latent space (see docs/ARCHITECTURE.md), so the cache
builders here and the engine's chunked-admission path must zero/pad
identically — that invariant is what the bucketed/chunked parity tests
pin down.

Decode-path distribution: the KV cache sequence dim is sharded over the mesh
axes returned by ``sharding.partition.seq_shard_axes`` and attention runs
inside ``shard_map`` — chunk selection and the gathered flash attention are
fully shard-local; only the O(B·H) partial-softmax combine crosses shards
(DESIGN.md §2).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import sparse_attention as sa
from repro.core.abstracts import Pyramid, build_pyramid, num_levels, update_pyramid
from repro.models.common import rms_norm, rotate, softcap
from repro.models.params import ParamDef
from repro.sharding.ctx import constrain, constrain_priority


# ---------------------------------------------------------------------------
# Decode context: how decode shards the cache
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DecodeCtx:
    """Static decode-distribution info (None mesh => pure local execution)."""
    mesh: Optional[Mesh] = None
    seq_axes: Tuple[str, ...] = ()
    batch_axes: Tuple[str, ...] = ()

    @property
    def n_seq_shards(self) -> int:
        if self.mesh is None or not self.seq_axes:
            return 1
        return math.prod(self.mesh.shape[a] for a in self.seq_axes)


LOCAL_CTX = DecodeCtx()


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------


def gqa_params(cfg: ArchConfig) -> Dict[str, ParamDef]:
    d, hd = cfg.d_model, cfg.hd
    H, Hkv = cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": ParamDef((d, H * hd), ("embed", "heads")),
        "wk": ParamDef((d, Hkv * hd), ("embed", "kv")),
        "wv": ParamDef((d, Hkv * hd), ("embed", "kv")),
        "wo": ParamDef((H * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = ParamDef((hd,), (None,), init="ones")
        p["k_norm"] = ParamDef((hd,), (None,), init="ones")
    return p


def mla_params(cfg: ArchConfig) -> Dict[str, ParamDef]:
    assert cfg.mla is not None
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "wkv_a": ParamDef((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None)),
        "kv_norm": ParamDef((m.kv_lora_rank,), (None,), init="ones"),
        "wk_b": ParamDef((H, m.kv_lora_rank, m.qk_nope_head_dim), ("heads", None, None)),
        "wv_b": ParamDef((H, m.kv_lora_rank, m.v_head_dim), ("heads", None, None)),
        "wo": ParamDef((H * m.v_head_dim, d), ("heads", "embed")),
    }
    if m.q_lora_rank:
        p["wq_a"] = ParamDef((d, m.q_lora_rank), ("embed", None))
        p["q_norm_a"] = ParamDef((m.q_lora_rank,), (None,), init="ones")
        p["wq_b"] = ParamDef((m.q_lora_rank, H * qk), (None, "heads"))
    else:
        p["wq"] = ParamDef((d, H * qk), ("embed", "heads"))
    return p


def attn_params(cfg: ArchConfig) -> Dict[str, ParamDef]:
    return mla_params(cfg) if cfg.mla is not None else gqa_params(cfg)


# ---------------------------------------------------------------------------
# Cache definitions
# ---------------------------------------------------------------------------


def gqa_cache_defs(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, ParamDef]:
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    chunk = cfg.leoam.chunk_size
    defs = {
        "k": ParamDef((batch, max_len, Hkv, hd), ("batch", "kv_seq", "kv", None), init="zeros"),
        "v": ParamDef((batch, max_len, Hkv, hd), ("batch", "kv_seq", "kv", None), init="zeros"),
    }
    if cfg.leoam.enabled:
        nc0 = max_len // chunk
        for lvl in range(num_levels(nc0, cfg.leoam.pyramid_levels)):
            nc = nc0 >> lvl
            defs[f"kmax{lvl}"] = ParamDef((batch, nc, Hkv, hd),
                                          ("batch", "kv_seq", "kv", None),
                                          init="zeros", dtype="float32")
            defs[f"kmin{lvl}"] = ParamDef((batch, nc, Hkv, hd),
                                          ("batch", "kv_seq", "kv", None),
                                          init="zeros", dtype="float32")
    return defs


def mla_cache_defs(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, ParamDef]:
    assert cfg.mla is not None
    m = cfg.mla
    chunk = cfg.leoam.chunk_size
    defs = {
        "ckv": ParamDef((batch, max_len, m.kv_lora_rank), ("batch", "kv_seq", None), init="zeros"),
        "krope": ParamDef((batch, max_len, m.qk_rope_head_dim), ("batch", "kv_seq", None), init="zeros"),
    }
    if cfg.leoam.enabled:
        nc0 = max_len // chunk
        for lvl in range(num_levels(nc0, cfg.leoam.pyramid_levels)):
            nc = nc0 >> lvl
            for nm, dim in (("cmax", m.kv_lora_rank), ("cmin", m.kv_lora_rank),
                            ("rmax", m.qk_rope_head_dim), ("rmin", m.qk_rope_head_dim)):
                defs[f"{nm}{lvl}"] = ParamDef((batch, nc, 1, dim),
                                              ("batch", "kv_seq", None, None),
                                              init="zeros", dtype="float32")
    return defs


def cache_defs(cfg: ArchConfig, kind: str, batch: int, max_len: int):
    if not kind.startswith("attn"):
        return None
    if cfg.mla is not None:
        return mla_cache_defs(cfg, batch, max_len)
    return gqa_cache_defs(cfg, batch, max_len)


def _pyr_from_cache(cache: Dict[str, jax.Array], prefix: str = "k") -> Pyramid:
    kmaxs, kmins, lvl = [], [], 0
    while f"{prefix}max{lvl}" in cache:
        kmaxs.append(cache[f"{prefix}max{lvl}"])
        kmins.append(cache[f"{prefix}min{lvl}"])
        lvl += 1
    return Pyramid(tuple(kmaxs), tuple(kmins))


# ---------------------------------------------------------------------------
# Blocked causal attention (train / prefill)
# ---------------------------------------------------------------------------


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      attn_softcap: Optional[float] = None,
                      block_q: int = 512, block_kv: int = 1024,
                      cross: bool = False, q_offset=0) -> jax.Array:
    """Flash-style attention: full query rows × scanned KV blocks.

    q: (B, S, H, hd) pre-scaled; k/v: (B, Skv, Hkv, hd).  Shardability is
    the design driver: queries keep a flat head dim (sharded over ``model``
    when H divides, else the S dim is sharded) and KV blocks are expanded to
    H heads *inside* the scan (a local slice of replicated KV) — no
    collective ever lands inside the loop.  O(S·block) memory.
    ``cross=True`` disables the causal mask (encoder-decoder).

    ``q_offset`` (static or traced scalar) places the query rows at global
    positions ``q_offset + [0, S)`` against the keys' absolute positions —
    the chunked-prefill path attends one prompt chunk against the whole
    (zero-initialised) decode cache, and the causal mask alone keeps
    not-yet-written / padding key rows out of every valid query row.
    """
    B, S, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    vd = v.shape[-1]                                     # may differ (MLA)
    G = H // Hkv
    bkv = min(block_kv, Skv)
    nkv = Skv // bkv
    assert Skv % bkv == 0, (Skv, bkv)

    q = constrain_priority(q, ("batch", None, "heads", None),
                           ("batch", "act_seq", None, None))
    k = constrain(k, ("batch", None, None, None))        # replicated / model
    v = constrain(v, ("batch", None, None, None))
    kb = k.reshape(B, nkv, bkv, Hkv, hd)
    vb = v.reshape(B, nkv, bkv, Hkv, vd)
    q_pos = jnp.arange(S) + q_offset

    def kv_step(carry, kj_and_kv):
        num, den, m = carry
        kj, kblk, vblk = kj_and_kv
        kh = jnp.repeat(kblk, G, axis=2)                 # (B,bkv,H,hd) local
        vh = jnp.repeat(vblk, G, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kh,
                       preferred_element_type=jnp.float32)
        if attn_softcap is not None:
            s = attn_softcap * jnp.tanh(s / attn_softcap)
        k_pos = kj * bkv + jnp.arange(bkv)
        mask = jnp.ones((S, bkv), bool)
        if causal and not cross:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, sa.NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        scale_old = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        e = jnp.exp(s - m_safe[..., None])
        e = jnp.where(mask[None, None], e, 0.0)
        num = num * scale_old[..., None] + jnp.einsum(
            "bhqk,bkhv->bhqv", e, vh.astype(jnp.float32))
        den = den * scale_old + jnp.sum(e, axis=-1)
        return (num, den, m_new), None

    init = (jnp.zeros((B, H, S, vd), jnp.float32),
            jnp.zeros((B, H, S), jnp.float32),
            jnp.full((B, H, S), sa.NEG_INF, jnp.float32))
    (num, den, _), _ = jax.lax.scan(
        kv_step, init,
        (jnp.arange(nkv), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
    den = jnp.where(den == 0.0, 1.0, den)
    out = jnp.moveaxis(num / den[..., None], 1, 2)       # (B,S,H,vd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA layer
# ---------------------------------------------------------------------------


def _qkv(p, cfg: ArchConfig, x: jax.Array, pos) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rotate(cfg, q, pos)
    k = rotate(cfg, k, pos)
    return q, k, v


def gqa_train(p, cfg: ArchConfig, kind: str, x: jax.Array, pos,
              cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              causal: bool = True) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, S, d = x.shape
    H, hd = cfg.n_heads, cfg.hd
    if cross_kv is not None:
        # cross-attention: no RoPE (keys are un-rotated encoder projections)
        q = (x @ p["wq"]).reshape(B, S, H, hd)
        k, v = cross_kv
        causal = False
    else:
        q, k, v = _qkv(p, cfg, x, pos)
    window = cfg.window if kind == "attn_local" else None
    out = blocked_attention(
        q * (1.0 / math.sqrt(hd)), k, v, causal=causal, window=window,
        attn_softcap=cfg.attn_softcap,
        block_q=cfg.runtime.attn_block_q, block_kv=cfg.runtime.attn_block_kv)
    return out.reshape(B, S, H * hd) @ p["wo"]


def cross_kv(p, cfg: ArchConfig, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Encoder-output K/V for cross-attention (computed once per request)."""
    B, S, d = enc_out.shape
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    k = (enc_out @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (enc_out @ p["wv"]).reshape(B, S, Hkv, hd)
    return k, v


def gqa_prefill_cache(cfg: ArchConfig, k: jax.Array, v: jax.Array,
                      max_len: int, length) -> Dict[str, jax.Array]:
    """Build the decode cache (padded KV + abstract pyramid) after prefill.

    Rows at positions >= ``length`` are zeroed before the pad: with bucketed
    prefill the prompt rides in padded to a bucket size, and the tier store
    ingests this cache — zeroing the bucket-padding rows keeps the stored
    chunks (and their min/max abstracts) bit-identical to exact-length
    prefill, whose pad rows were already zeros."""
    B, S, Hkv, hd = k.shape
    valid = (jnp.arange(S, dtype=jnp.int32)
             < jnp.asarray(length, jnp.int32))[None, :, None, None]
    k = jnp.where(valid, k, 0)
    v = jnp.where(valid, v, 0)
    pad = max_len - S
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # pin to the decode layout NOW — otherwise the prefill layer scan stacks
    # every layer's cache replicated before one big reshard (observed tens
    # of GiB of scan-ys buffering on the 32k prefill cells)
    kp = constrain(kp, ("batch", "kv_seq", "kv", None))
    vp = constrain(vp, ("batch", "kv_seq", "kv", None))
    cache = {"k": kp, "v": vp}
    if cfg.leoam.enabled:
        chunk = cfg.leoam.chunk_size
        pyr = build_pyramid(kp, chunk, cfg.leoam.pyramid_levels, length=length)
        for lvl in range(pyr.levels):
            cache[f"kmax{lvl}"] = constrain(pyr.kmax[lvl],
                                            ("batch", "kv_seq", "kv", None))
            cache[f"kmin{lvl}"] = constrain(pyr.kmin[lvl],
                                            ("batch", "kv_seq", "kv", None))
    return cache


def _layer_budget(cfg: ArchConfig, layer_idx: int, n_local_chunks: int,
                  n_seq_shards: int = 1) -> int:
    lcfg = cfg.leoam
    rate = lcfg.early_rate if layer_idx < lcfg.early_layers else lcfg.importance_rate
    # global sink/recent forcing (§Perf C3): with >1 sequence shard, no
    # single shard hosts both the sink and the tail, so the static budget
    # only reserves max(sink, recent) slots instead of their sum
    if n_seq_shards > 1:
        forced = max(lcfg.sink_chunks, lcfg.recent_chunks)
    else:
        forced = lcfg.sink_chunks + lcfg.recent_chunks
    want = int(math.ceil(n_local_chunks * rate)) + forced
    return max(1, min(n_local_chunks, want))


def gqa_decode(p, cfg: ArchConfig, kind: str, x: jax.Array,
               cache: Dict[str, jax.Array], length: jax.Array, *,
               layer_idx: int, ctx: DecodeCtx = LOCAL_CTX,
               cross_kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step.  x: (B, 1, d); length: scalar current cache length."""
    B, _, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    scale = 1.0 / math.sqrt(hd)

    if cross_kv_cache is not None:
        q = (x @ p["wq"]).reshape(B, H, hd)
        ck, cv = cross_kv_cache
        part = sa.dense_decode_gqa(q * scale, ck, cv, length=ck.shape[1])
        out = sa._finish(part).astype(x.dtype)
        return (out.reshape(B, 1, H * hd) @ p["wo"]), cache

    pos = jnp.full((B, 1), length, jnp.int32)
    if cfg.rope == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    q, k_new, v_new = _qkv(p, cfg, x, pos)
    q = (q[:, 0] * scale)                                    # (B, H, hd)
    k_new, v_new = k_new[:, 0], v_new[:, 0]                  # (B, Hkv, hd)

    S_total = cache["k"].shape[1]
    chunk = cfg.leoam.chunk_size
    use_sparse = (cfg.leoam.enabled and kind != "attn_local"
                  and S_total >= cfg.leoam.min_seq_for_sparse)
    window = cfg.window if kind == "attn_local" else None

    # NOTE (§Perf C2, refuted): moving the cache write OUTSIDE the
    # shard_map (global DUS on the sharded seq dim, letting SPMD localize
    # it) was measured WORSE — XLA partitions a traced-index DUS on a
    # sharded dim with cache-scale collective traffic (22 MB -> 1.7 GiB
    # per step).  Writes stay inside the manual region, conditioned on the
    # owner shard, touching only the written slice.
    def local_fn(q, k_new, v_new, length, *cache_leaves):
        names = sorted(cache.keys())
        c = dict(zip(names, cache_leaves))
        S_l = c["k"].shape[1]
        if ctx.seq_axes:
            shard_idx = jax.lax.axis_index(ctx.seq_axes).astype(jnp.int32)
        else:
            shard_idx = jnp.int32(0)
        owner = (length // S_l) == shard_idx
        wpos = (length % S_l).astype(jnp.int32)
        old_k = jax.lax.dynamic_slice_in_dim(c["k"], wpos, 1, axis=1)
        old_v = jax.lax.dynamic_slice_in_dim(c["v"], wpos, 1, axis=1)
        new_k = jnp.where(owner, k_new[:, None].astype(c["k"].dtype), old_k)
        new_v = jnp.where(owner, v_new[:, None].astype(c["v"].dtype), old_v)
        c["k"] = jax.lax.dynamic_update_slice_in_dim(c["k"], new_k, wpos, axis=1)
        c["v"] = jax.lax.dynamic_update_slice_in_dim(c["v"], new_v, wpos, axis=1)
        if use_sparse:
            pyr = _pyr_from_cache(c)
            k_eff = jnp.where(owner, k_new.astype(jnp.float32),
                              jnp.full_like(k_new, -jnp.inf, jnp.float32))
            k_eff_min = jnp.where(owner, k_new.astype(jnp.float32),
                                  jnp.full_like(k_new, jnp.inf, jnp.float32))
            pyr = Pyramid(
                update_pyramid(pyr, k_eff, wpos, chunk).kmax,
                update_pyramid(Pyramid(pyr.kmax, pyr.kmin), k_eff_min,
                               wpos, chunk).kmin)
            for lvl in range(pyr.levels):
                c[f"kmax{lvl}"] = pyr.kmax[lvl]
                c[f"kmin{lvl}"] = pyr.kmin[lvl]
        local_len = jnp.clip(length + 1 - shard_idx * S_l, 0, S_l)
        if use_sparse:
            budget = _layer_budget(cfg, layer_idx, S_l // chunk,
                                   ctx.n_seq_shards)
            # sink/recent forcing is in GLOBAL chunk coordinates (§Perf C3)
            global_valid = (length + chunk) // chunk
            offset = shard_idx * (S_l // chunk)
            part = sa.leoam_decode_shard(
                q, c["k"], c["v"], pyr, chunk=chunk, budget=budget,
                length=local_len, attn_softcap=cfg.attn_softcap,
                sink_chunks=cfg.leoam.sink_chunks,
                recent_chunks=cfg.leoam.recent_chunks,
                rf=cfg.leoam.refine_factor, n_valid_chunks=global_valid,
                chunk_offset=offset)
        else:
            part = sa.dense_decode_gqa(
                q, c["k"], c["v"], length=local_len,
                attn_softcap=cfg.attn_softcap, window=window,
                base_pos=shard_idx * S_l, query_pos=length)
        out = sa.combine_partials(part, ctx.seq_axes)
        return (out, *[c[n] for n in names])

    names = sorted(cache.keys())
    if ctx.seq_axes:
        db = ctx.batch_axes
        cache_spec = {
            n: P(db or None, ctx.seq_axes if len(ctx.seq_axes) > 1 else ctx.seq_axes[0],
                 *([None] * (cache[n].ndim - 2))) for n in names}
        fn = jax.shard_map(
            local_fn, mesh=ctx.mesh,
            in_specs=(P(db or None, None, None), P(db or None, None, None),
                      P(db or None, None, None), P(),
                      *[cache_spec[n] for n in names]),
            out_specs=(P(db or None, None, None), *[cache_spec[n] for n in names]),
            check_vma=False)
    else:
        fn = local_fn
    out, *new_leaves = fn(q, k_new, v_new, length, *[cache[n] for n in names])
    new_cache = dict(zip(names, new_leaves))
    out = out.astype(x.dtype).reshape(B, 1, H * hd)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLA layer (DeepSeek): absorbed decode, latent-space LeoAM selection
# ---------------------------------------------------------------------------


def _mla_q(p, cfg: ArchConfig, x: jax.Array, pos) -> Tuple[jax.Array, jax.Array]:
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        qa = rms_norm(x @ p["wq_a"], p["q_norm_a"], cfg.norm_eps)
        q = (qa @ p["wq_b"]).reshape(B, S, H, qk)
    else:
        q = (x @ p["wq"]).reshape(B, S, H, qk)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = rotate(cfg, q[..., m.qk_nope_head_dim:], pos)
    return q_nope, q_rope


def mla_train(p, cfg: ArchConfig, kind: str, x: jax.Array, pos) -> jax.Array:
    """Non-absorbed MLA for train/prefill (materializes per-head K/V)."""
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(p, cfg, x, pos)
    kv_a = x @ p["wkv_a"]
    ckv = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = rotate(cfg, kv_a[..., None, m.kv_lora_rank:], pos)   # (B,S,1,rr)
    k_nope = jnp.einsum("bsr,hrd->bshd", ckv, p["wk_b"])
    val = jnp.einsum("bsr,hrd->bshd", ckv, p["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, S, H, m.qk_rope_head_dim))], -1)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    out = blocked_attention(q * scale, k, val, causal=True,
                            block_q=cfg.runtime.attn_block_q,
                            block_kv=cfg.runtime.attn_block_kv)
    return out.reshape(B, S, H * m.v_head_dim) @ p["wo"]


def mla_prefill_cache(p, cfg: ArchConfig, x: jax.Array, pos, max_len: int,
                      length) -> Dict[str, jax.Array]:
    """Build the absorbed-MLA decode cache (latent ckv/krope + abstract
    pyramids) after prefill.

    ``length`` (static or traced) marks the prompt's true length under
    bucketed prefill: rows at positions >= length are zeroed BEFORE the
    max_len pad, exactly as :func:`gqa_prefill_cache` — the serving
    engine ingests these latents into its single-plane tier store
    (concat(ckv, krope) per token), so bucket-padding rows must match
    the exact-length path bit-for-bit for chunk replicas and min/max
    abstracts to agree."""
    m = cfg.mla
    B, S, _ = x.shape
    kv_a = x @ p["wkv_a"]
    ckv = rms_norm(kv_a[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope = rotate(cfg, kv_a[..., None, m.kv_lora_rank:], pos)[:, :, 0]
    # zero bucket-padding rows (see gqa_prefill_cache)
    valid = (jnp.arange(S, dtype=jnp.int32)
             < jnp.asarray(length, jnp.int32))[None, :, None]
    ckv = jnp.where(valid, ckv, 0)
    krope = jnp.where(valid, krope, 0)
    pad = max_len - S
    ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
    krope = jnp.pad(krope, ((0, 0), (0, pad), (0, 0)))
    ckv = constrain(ckv, ("batch", "kv_seq", None))
    krope = constrain(krope, ("batch", "kv_seq", None))
    cache = {"ckv": ckv, "krope": krope}
    if cfg.leoam.enabled:
        chunk = cfg.leoam.chunk_size
        cs = ("batch", "kv_seq", None, None)
        pc = build_pyramid(ckv[:, :, None], chunk, cfg.leoam.pyramid_levels,
                           length=length)
        pr = build_pyramid(krope[:, :, None], chunk, cfg.leoam.pyramid_levels,
                           length=length)
        for lvl in range(pc.levels):
            cache[f"cmax{lvl}"] = constrain(pc.kmax[lvl], cs)
            cache[f"cmin{lvl}"] = constrain(pc.kmin[lvl], cs)
            cache[f"rmax{lvl}"] = constrain(pr.kmax[lvl], cs)
            cache[f"rmin{lvl}"] = constrain(pr.kmin[lvl], cs)
    return cache


def mla_decode(p, cfg: ArchConfig, kind: str, x: jax.Array,
               cache: Dict[str, jax.Array], length: jax.Array, *,
               layer_idx: int, ctx: DecodeCtx = LOCAL_CTX
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    m = cfg.mla
    B, _, d = x.shape
    H = cfg.n_heads
    pos = jnp.full((B, 1), length, jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, pos)
    # absorb W_UK into the query: q_lat = q_nope @ W_UK  -> latent space
    q_lat = jnp.einsum("bhd,hrd->bhr", q_nope[:, 0], p["wk_b"])
    q_rope = q_rope[:, 0]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_lat, q_rope = q_lat * scale, q_rope * scale

    kv_a = (x @ p["wkv_a"])[:, 0]
    ckv_new = rms_norm(kv_a[:, : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    krope_new = rotate(cfg, kv_a[:, None, None, m.kv_lora_rank:], pos)[:, 0, 0]

    S_total = cache["ckv"].shape[1]
    chunk = cfg.leoam.chunk_size
    use_sparse = (cfg.leoam.enabled and S_total >= cfg.leoam.min_seq_for_sparse)

    # writes stay inside the manual region (see §Perf C2 note in gqa_decode)
    def local_fn(q_lat, q_rope, ckv_new, krope_new, length, *cache_leaves):
        names = sorted(cache.keys())
        c = dict(zip(names, cache_leaves))
        S_l = c["ckv"].shape[1]
        if ctx.seq_axes:
            shard_idx = jax.lax.axis_index(ctx.seq_axes).astype(jnp.int32)
        else:
            shard_idx = jnp.int32(0)
        owner = (length // S_l) == shard_idx
        wpos = (length % S_l).astype(jnp.int32)
        old_ck = jax.lax.dynamic_slice_in_dim(c["ckv"], wpos, 1, axis=1)
        old_kr = jax.lax.dynamic_slice_in_dim(c["krope"], wpos, 1, axis=1)
        new_ck = jnp.where(owner, ckv_new[:, None].astype(c["ckv"].dtype), old_ck)
        new_kr = jnp.where(owner, krope_new[:, None].astype(c["krope"].dtype), old_kr)
        c["ckv"] = jax.lax.dynamic_update_slice_in_dim(c["ckv"], new_ck, wpos, axis=1)
        c["krope"] = jax.lax.dynamic_update_slice_in_dim(c["krope"], new_kr, wpos, axis=1)
        if use_sparse:
            def upd_pyr(pyr, vec):
                hi = jnp.where(owner, vec.astype(jnp.float32),
                               jnp.full_like(vec, -jnp.inf, jnp.float32))
                lo = jnp.where(owner, vec.astype(jnp.float32),
                               jnp.full_like(vec, jnp.inf, jnp.float32))
                return Pyramid(update_pyramid(pyr, hi, wpos, chunk).kmax,
                               update_pyramid(pyr, lo, wpos, chunk).kmin)
            pc = upd_pyr(_pyr_from_cache(c, "c"), ckv_new[:, None])
            pr = upd_pyr(_pyr_from_cache(c, "r"), krope_new[:, None])
            for lvl in range(pc.levels):
                c[f"cmax{lvl}"], c[f"cmin{lvl}"] = pc.kmax[lvl], pc.kmin[lvl]
                c[f"rmax{lvl}"], c[f"rmin{lvl}"] = pr.kmax[lvl], pr.kmin[lvl]
        local_len = jnp.clip(length + 1 - shard_idx * S_l, 0, S_l)
        if use_sparse:
            budget = _layer_budget(cfg, layer_idx, S_l // chunk,
                                   ctx.n_seq_shards)
            global_valid = (length + chunk) // chunk
            offset = shard_idx * (S_l // chunk)
            from repro.core.adaptive import pyramid_select_mla
            ids = pyramid_select_mla(q_lat, q_rope, pc, pr, budget,
                                     rf=cfg.leoam.refine_factor,
                                     sink_chunks=cfg.leoam.sink_chunks,
                                     recent_chunks=cfg.leoam.recent_chunks,
                                     n_valid0=global_valid,
                                     chunk_offset=offset)
            part = sa.sparse_decode_mla(q_lat, q_rope, c["ckv"], c["krope"],
                                        ids, chunk, length=local_len)
        else:
            part = sa.dense_decode_mla(q_lat, q_rope, c["ckv"], c["krope"],
                                       length=local_len)
        out_lat = sa.combine_partials(part, ctx.seq_axes)     # (B,H,r)
        return (out_lat, *[c[n] for n in names])

    names = sorted(cache.keys())
    if ctx.seq_axes:
        db = ctx.batch_axes
        seqs = ctx.seq_axes if len(ctx.seq_axes) > 1 else ctx.seq_axes[0]
        cache_spec = {n: P(db or None, seqs, *([None] * (cache[n].ndim - 2)))
                      for n in names}
        fn = jax.shard_map(
            local_fn, mesh=ctx.mesh,
            in_specs=(P(db or None, None, None), P(db or None, None, None),
                      P(db or None, None), P(db or None, None), P(),
                      *[cache_spec[n] for n in names]),
            out_specs=(P(db or None, None, None), *[cache_spec[n] for n in names]),
            check_vma=False)
    else:
        fn = local_fn
    out_lat, *new_leaves = fn(q_lat, q_rope, ckv_new, krope_new, length,
                              *[cache[n] for n in names])
    new_cache = dict(zip(names, new_leaves))
    # absorbed value up-projection: (B,H,r) @ (H,r,vd) -> (B,H,vd)
    out = jnp.einsum("bhr,hrv->bhv", out_lat.astype(jnp.float32),
                     p["wv_b"].astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(B, 1, H * m.v_head_dim)
    return out @ p["wo"], new_cache
