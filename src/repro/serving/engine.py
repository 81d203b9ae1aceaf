"""LeoAM serving engine: real batched tiered decoding on a live model.

The engine exercises every paper mechanism with genuine data movement:
prefill populates the three-tier store (full replicas + abstracts on disk),
each decode round evaluates chunk importance on the host from abstracts
(IAKM tree or flat selection), fetches ONLY the selected chunks through the
transit codec, attends over the assembled working set on device, and appends
the new token's KV + abstract update.  An access-frequency table pins hot
chunks above the disk tier.  Traffic is audited by the TieredKVStore log —
benchmarks assert the LKA ratio r = α + 2/n' on it.

The decode round is the paper's Dynamic Three-tier Pipeline (§4.4), three
stages per attention layer:

1. **Evaluate** (CPU): one ``chunk_bounds_gqa_matmul`` over the stacked
   per-request queries and the layer's (padded) abstract stack, then
   chunk-level adaptive selection (IAKM tree or flat) per sequence —
   importance evaluation amortizes across the batch.
2. **Transfer** (disk→host→device): one batch-coalesced disk gather stages
   cold chunks host-side; the device-resident chunk pool
   (:class:`~repro.serving.offload.DeviceChunkPool`) then uploads ONLY the
   newly-promoted delta — pool-resident chunks cost zero bytes.  With
   ``real_codec`` the θ-fraction of the delta crosses the link as packed
   int4/int8 payloads and is dequantized on device
   (``kernels.kv_quant``); θ is chosen per layer each round by the paper's
   balance ``optimal_theta`` from measured compute/transfer costs.
3. **Attend** (GPU): one jitted dispatch gathers the working set from the
   pool by slot index and runs padded+masked attention — ragged
   per-sequence selections are padded to the round's (bucketed) max, which
   is FP-exact: padded keys score -inf, contribute exp(-inf)=0, and adding
   zeros never perturbs the f32 accumulators.

The layer's dense work around the three stages, norm/Q/K/V/rotary before
and the residual add and MLP after, runs as two compiled programs per
layer position (:func:`_layer_fn`); a scanned layer's programs take the
stacked weights and a device repeat index and slice them on device.

With ``pipeline=True`` a one-worker prefetch executor overlaps stage 2 of
layer l+1 under stage 3 of layer l: while layer l's attention runs, the
worker reads layer l+1's abstracts and speculatively stages its predicted
selection (previous round's selection, else the AccessTable hot set)
disk→host.  Predictions only move residency, never values — a miss falls
back to the synchronous path, so pipelined output is bit-identical to
``pipeline=False``.

The ADMISSION path is pipelined too (PR 3): ``add_sequence`` streams each
attention layer's K/V into the tier store as it is forced off the device,
with the disk replica + abstract writes running write-behind on the shared
prefetch executor under the remaining layers' prefill compute
(``overlap_ingest``; a per-sequence completion fence at decode-round entry
and release keeps every read ordered after the writes).
``add_sequence_async`` runs the whole prefill+ingest on a one-worker
admission executor so new requests admit UNDER the active batch's decode
rounds — only the store's lock-protected critical sections serialize, and
the new sequence defers device-pool placement so the decode thread's
attention gathers never race a pool scatter.  Both are token-identical to
the serial path (tested): write-behind moves bytes, never values.

Admission is BUCKETED and CHUNKABLE (PR 4): ``_prefill`` pads the prompt
to a power-of-two length bucket and threads the true length through the
jitted program (logits row, cache zeroing, recurrent-state masking), so
O(log max_len) compiled programs serve any public-traffic length mix —
token-identical to exact-length prefill (property-tested at bucket
edges).  ``begin_admission`` returns a resumable :class:`ChunkedAdmission`
that forces one fixed-size prefill chunk per ``step()`` (ONE compiled
program for every chunk of every prompt — offset-causal attention over
the zero-initialised decode cache) and streams each chunk into the store
through chunk-aligned partial ingest, so the scheduler can run decode
rounds between a long prompt's chunks instead of stalling behind its
whole prefill.

DeepSeek-class absorbed-MLA models ride the SAME pipeline (PR 5): the
tier store keeps one latent plane per token (concat(c_kv, k_rope), a
single logical kv head of width kv_lora_rank + qk_rope_head_dim) instead
of a K/V pair, importance evaluation reuses the positive/negative-split
bounds matmul against latent min/max boxes (q_lat·ckv + q_rope·krope is
exactly the concatenated dot product), the pooled/legacy dispatches
gather latent rows and apply the absorbed W_UV once after the softmax,
and both whole-prompt AND chunked admission stream latent rows through
``ingest`` — so ``ContinuousBatcher(chunked_admission=True)`` serves MLA
traffic with the same O(log L) compiled-program and bounded-stall
guarantees as GQA (property-tested token-identical).

``pooled=False, pipeline=False`` reproduces the PR-1 synchronous engine
(full working-set re-upload per layer) for A/B tests and benchmarks;
``overlap_ingest=False`` reproduces the PR-2 serial admission path;
``bucket_prefill=False`` reproduces the PR-3 compile-per-length prefill.

``LeoAMEngine`` is the single-sequence view: a thin wrapper over a B=1
batched engine preserving the original prefill/decode_step/generate API.
"""

from __future__ import annotations

import functools
import math
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import compression
from repro.core import pipeline as dtp
from repro.core.adaptive import flat_select_chunks, tree_select_chunks
from repro.core.bounds import chunk_bounds_gqa_matmul
from repro.core.tiers import AccessTable
from repro.kernels.pq import adc_chunk_scores
from repro.models import lm
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.serving.faults import AdmissionError, ChunkLostError
from repro.serving.offload import DEVICE, DISK, HOST, TieredKVStore
from repro.serving.sanitizer import decode_thread_only, worker_thread
from repro.serving.tracing import Round, span


@dataclass
class EngineCfg:
    max_len: int = 1024
    gpu_chunk_frac: float = 0.15     # device-resident fraction
    cpu_chunk_frac: float = 0.45     # host tier fraction (rest -> disk)
    selection: str = "tree"          # tree | flat
    hot_frac: float = 0.05
    transit_codec: Optional[str] = "int4"
    sel_pad: int = 4                 # pad round working sets to a multiple
                                     # of this many chunks (bounds jit
                                     # recompiles; masking keeps it exact)
    pooled: bool = True              # device-resident chunk pool (delta
                                     # uploads); False = PR-1 full re-upload
    pipeline: bool = True            # async DTP overlap (prefetch thread)
    real_codec: bool = False         # carry actual packed int4/int8 transit
                                     # payloads (vs ledger-only scaling)
    overlap_ingest: bool = True      # write-behind prefill ingest: replica/
                                     # abstract writes ride the shared
                                     # prefetch executor under the next
                                     # layer's prefill compute (fenced);
                                     # False = PR-2 serial ingest
    jit_prefill: bool = True         # compile lm.prefill per prompt length
                                     # (one XLA call per admission instead
                                     # of thousands of GIL-bound op
                                     # dispatches — admission under decode
                                     # then truly overlaps, and TTFT drops
                                     # even standalone)
    bucket_prefill: bool = True      # pad prompts to power-of-two (or
                                     # prefill_buckets) lengths with a
                                     # validity mask: O(log max_len)
                                     # compiled programs serve EVERY prompt
                                     # length, token-identical to
                                     # exact-length prefill (tested);
                                     # False = PR-3 one program per length
    prefill_buckets: Optional[Tuple[int, ...]] = None
                                     # explicit ascending bucket schedule
                                     # (None = powers of two from 16)
    prefill_chunk_tokens: int = 64   # chunk size for begin_admission's
                                     # resumable chunked prefill; must
                                     # divide max_len and be a multiple of
                                     # the store chunk
    sidecar_requant: bool = True     # background sweep re-packs append-
                                     # dirtied disk sidecars once a chunk
                                     # goes a full round without appends
                                     # (no-op unless disk_sidecar)
    disk_sidecar: bool = False       # packed int4/int8 disk replicas: tier
                                     # writes + disk->host promotions move
                                     # packed bytes (fp16 stays as the
                                     # lossless fallback)
    sidecar_lossless: bool = False   # flag the fallback on: promotions
                                     # read the fp16 replica (full bytes)
                                     # even when the sidecar is valid
    pq_abstracts: bool = False       # PQ abstract plane: per-layer online
                                     # k-means codebooks over ingested key
                                     # chunks; importance evaluation scores
                                     # code-valid chunks via the ADC lookup
                                     # table (codes are a fraction of the
                                     # min/max box bytes), falling back
                                     # BITWISE to the bounds matmul for
                                     # append-dirtied/corrupt chunks; off
                                     # = the exact min/max path, untouched
    pq_m: Optional[int] = None       # key subvectors per head dim (None =
                                     # head_dim // 8)
    pq_centroids: int = 256          # codebook entries per subspace
                                     # (uint8 codes: <= 256; the codebook
                                     # is shared per-layer state, so more
                                     # centroids sharpen ADC at zero
                                     # per-chunk byte cost)
    pq_train_iters: int = 4          # Lloyd iterations on the first
                                     # (codebook-initializing) ingest
    prefix_cache: bool = False       # content-addressable cross-request
                                     # shared-prefix reuse: warm prompts
                                     # adopt matching chunk-aligned spans
                                     # by reference (zero prefill FLOPs,
                                     # zero duplicate tier bytes) and
                                     # resume chunked prefill at the cold
                                     # suffix; opt-in — admission routes
                                     # through the chunked-prefill path
    prefix_arena_rows: int = 8       # shared-chunk arena rows appended to
                                     # the store's per-seq arrays; bounds
                                     # how many distinct prefix sets stay
                                     # resident (LRU beyond that)
    profile: bool = False            # block per stage, fill round_profiles
    debug_sync: bool = False         # runtime sync-sanitizer: ownership
                                     # decorators assert the owning
                                     # thread, store/pool mutators get a
                                     # concurrent-entry epoch guard, and
                                     # the store locks feed a lock-order
                                     # tracker that fails on cycles.  For
                                     # debugging/stress only — never for
                                     # measured runs (benchmarks/run.py
                                     # refuses)
    checksums: bool = True           # per-chunk CRC32 on disk replicas +
                                     # packed sidecars, verified at every
                                     # promotion: a corrupt sidecar falls
                                     # back to the fp16 replica, a corrupt
                                     # replica triggers recompute-from-
                                     # prompt (or seq-level failure)
    fault_plan: Optional[Any] = None  # serving.faults.FaultPlan threaded
                                     # through the store's I/O choke
                                     # points (chaos tests only)
    io_retries: int = 3              # bounded retry budget on transient
    io_backoff_s: float = 1e-4       # disk errors, exponential backoff
    # measured-cost θ balance (paper §4.4); defaults mirror TierBW
    pcie_bw: float = 16e9
    disk_bw: float = 3.5e9
    kappa: float = 1.0 / 80e9


# one process-wide DTP prefetch worker, shared by every pipelined engine:
# per-engine executors would leak a thread per engine (benchmark sweeps
# build dozens), and a single queue preserves per-engine FIFO ordering.
# Write-behind ingest rides the SAME worker: its FIFO order guarantees a
# layer's replica/abstract writes land before any prefetch submitted later,
# and the per-seq ingest fence covers everything else.
_PF_EXECUTOR: Optional[ThreadPoolExecutor] = None

# a separate one-worker admission executor runs whole add_sequence calls
# (prefill + ingest) under the active batch's decode rounds — on the DTP
# worker a long prefill would stall every decode round's prefetch
_ADMIT_EXECUTOR: Optional[ThreadPoolExecutor] = None


def _prefetch_executor() -> ThreadPoolExecutor:
    global _PF_EXECUTOR
    if _PF_EXECUTOR is None:
        _PF_EXECUTOR = ThreadPoolExecutor(max_workers=1,
                                          thread_name_prefix="leoam-dtp")
    return _PF_EXECUTOR


def _admit_executor() -> ThreadPoolExecutor:
    global _ADMIT_EXECUTOR
    if _ADMIT_EXECUTOR is None:
        _ADMIT_EXECUTOR = ThreadPoolExecutor(max_workers=1,
                                             thread_name_prefix="leoam-admit")
    return _ADMIT_EXECUTOR


@dataclass
class StepStats:
    evaluations: int = 0
    fetched_chunks: int = 0
    fetched_bytes: float = 0.0
    abstract_bytes: float = 0.0


@dataclass
class _SeqState:
    """Host-side per-sequence decode state (model cache + bookkeeping)."""
    cache: Any                       # non-attention state + dense caches
    length: int
    access: AccessTable
    stats: List[StepStats] = field(default_factory=list)
    tokens: Optional[np.ndarray] = None  # prompt tokens (recompute source
                                     # for disk-lost prompt-span chunks)
    prompt_len: int = 0              # tokens covered by the prompt — only
                                     # chunks entirely within this span
                                     # are recomputable (decode appends
                                     # exist nowhere but the lost replica)


def _attend_core(q, kg, vg, k_new, v_new, valid, wo, attn_softcap):
    """Padded-working-set attention shared by the pooled and legacy paths.

    q: (B, 1, H, hd) model dtype; kg/vg: (B, nmax, chunk, Hkv, hd) store
    dtype; k_new/v_new: (B, 1, Hkv, hd); valid: (B, 1, 1, S) bool with
    S = nmax*chunk + 1; wo: (H*hd, d).  Padded / beyond-length positions are
    masked to -inf before the softmax partials, so ragged per-sequence
    selections cost nothing numerically.
    """
    from repro.core import sparse_attention as sa
    B, _, H, hd = q.shape
    _, n, c, Hkv, _ = kg.shape
    G = H // Hkv
    kg = kg.reshape(B, n * c, Hkv, hd)
    vg = vg.reshape(B, n * c, Hkv, hd)
    kg = jnp.concatenate([kg.astype(q.dtype), k_new.astype(q.dtype)], axis=1)
    vg = jnp.concatenate([vg.astype(q.dtype), v_new.astype(q.dtype)], axis=1)
    qs = q[:, 0] * (1.0 / math.sqrt(hd))
    kt = jnp.swapaxes(kg, 1, 2)
    vt = jnp.swapaxes(vg, 1, 2)
    scores = jnp.einsum("bkgd,bksd->bkgs",
                        qs.reshape(B, Hkv, G, hd).astype(jnp.float32),
                        kt.astype(jnp.float32))
    if attn_softcap is not None:
        scores = attn_softcap * jnp.tanh(scores / attn_softcap)
    part = sa._masked_softmax_partials(scores, vt, valid)
    out = sa._finish(part).astype(q.dtype).reshape(B, 1, H * hd)
    return out @ wo


@functools.partial(jax.jit, static_argnames=("attn_softcap",))
def _attend_workingset(q, kg, vg, k_new, v_new, valid, wo, *,
                       attn_softcap: Optional[float]):
    """Legacy dispatch: host-assembled working set uploaded whole (PR-1)."""
    return _attend_core(q, kg, vg, k_new, v_new, valid, wo, attn_softcap)


@functools.partial(jax.jit, static_argnames=("attn_softcap",))
def _attend_pooled(q, pool_kv, slots, chunk_ids, lengths, k_new, v_new,
                   wo, *, attn_softcap: Optional[float]):
    """Pooled dispatch: gather the working set from the device slab by slot
    index — the only host→device traffic this op needs is the (B, nmax)
    ``slots``/``chunk_ids`` index arrays (the validity mask is derived on
    device, not uploaded).

    pool_kv: (n_slots + 1, 2, chunk, Hkv, hd); slots: (B, nmax) int32
    (padding rows point at slot 0); chunk_ids: (B, nmax) int32 with -1 on
    padding; lengths: (B,) int32."""
    kv = pool_kv[slots]                  # (B, nmax, 2, chunk, Hkv, hd)
    B, nmax = slots.shape
    chunk = pool_kv.shape[2]
    pos = (chunk_ids[..., None] * chunk
           + jnp.arange(chunk, dtype=jnp.int32)).reshape(B, nmax * chunk)
    # the store holds tokens 0..length-1 at attend time (this round's token
    # arrives via k_new/v_new, its append lands after the dispatch), so the
    # grid mask is STRICT — `pos == length` is an unwritten/stale row
    ok = (chunk_ids[..., None] >= 0).repeat(chunk, -1).reshape(B, -1) \
        & (pos < lengths[:, None])
    valid = jnp.concatenate(
        [ok, jnp.ones((B, 1), bool)], axis=1)[:, None, None]  # + new token
    return _attend_core(q, kv[:, :, 0], kv[:, :, 1], k_new, v_new, valid,
                        wo, attn_softcap)


def _attend_core_mla(q_lat, q_rope, lat, lat_new, valid, wv_b, wo):
    """Absorbed-MLA working-set attention shared by the pooled and legacy
    paths.

    q_lat: (B, H, r) and q_rope: (B, H, rr), both pre-scaled; lat: (B, S,
    D) gathered latent rows (D = r + rr, store dtype); lat_new: (B, D) the
    current token's latent row; valid: (B, 1, 1, S + 1) bool.  Scores are
    q_lat·ckv + q_rope·krope over the latent plane, the weighted sum stays
    in latent space, and W_UV is applied once afterwards (absorbed value
    projection) — masked rows contribute exact zeros, so ragged selections
    cost nothing numerically."""
    from repro.core import sparse_attention as sa
    B, H, r = q_lat.shape
    lat = jnp.concatenate([lat, lat_new[:, None].astype(lat.dtype)], axis=1)
    ckv, krope = lat[..., :r], lat[..., r:]
    scores = (jnp.einsum("bhr,bsr->bhs", q_lat.astype(jnp.float32),
                         ckv.astype(jnp.float32))
              + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                           krope.astype(jnp.float32)))
    # single logical kv head: reuse the shared masked partials with Hkv=1
    part = sa._masked_softmax_partials(scores[:, None],
                                       ckv[:, None], valid)
    out_lat = sa._finish(part)                               # (B, H, r)
    out = jnp.einsum("bhr,hrv->bhv", out_lat.astype(jnp.float32),
                     wv_b.astype(jnp.float32))
    return out.reshape(B, 1, -1).astype(q_lat.dtype) @ wo


@jax.jit
def _attend_pooled_mla(q_lat, q_rope, pool_kv, slots, chunk_ids, lengths,
                       lat_new, wv_b, wo):
    """Pooled MLA dispatch: gather latent chunk rows from the single-plane
    device slab by slot index (see :func:`_attend_pooled` for the
    masking/billing contract — identical, with D-wide latent rows in place
    of the K/V pair)."""
    lat = pool_kv[slots][:, :, 0]        # (B, nmax, chunk, 1, D)
    B, nmax = slots.shape
    chunk = pool_kv.shape[2]
    lat = lat.reshape(B, nmax * chunk, -1)
    pos = (chunk_ids[..., None] * chunk
           + jnp.arange(chunk, dtype=jnp.int32)).reshape(B, nmax * chunk)
    # strict mask, exactly as _attend_pooled: pos == length is unwritten
    ok = (chunk_ids[..., None] >= 0).repeat(chunk, -1).reshape(B, -1) \
        & (pos < lengths[:, None])
    valid = jnp.concatenate(
        [ok, jnp.ones((B, 1), bool)], axis=1)[:, None, None]
    return _attend_core_mla(q_lat, q_rope, lat, lat_new, valid, wv_b, wo)


@jax.jit
def _attend_workingset_mla(q_lat, q_rope, latg, lat_new, valid, wv_b, wo):
    """Legacy MLA dispatch: host-assembled latent working set uploaded
    whole (the PR-1 synchronous A/B path).  latg: (B, nmax, chunk, 1, D)."""
    B = latg.shape[0]
    lat = latg.reshape(B, latg.shape[1] * latg.shape[2], -1)
    return _attend_core_mla(q_lat, q_rope, lat, lat_new, valid, wv_b, wo)


def _pre_attention(cfg: ArchConfig, blk, h, pos) -> Dict[str, jax.Array]:
    """One attention layer's dense work before selection: the input norm,
    the projections (with ``qk_norm`` where the config has it) and rotary
    at ``pos`` (B, 1).  Returns what selection, attend and append read:
    GQA ``q`` (B, 1, H, hd), ``q_sel`` = q[:, 0], ``k_new``/``v_new``
    (B, 1, Hkv, hd); absorbed MLA the pre-scaled ``q_lat`` (B, H, r) and
    ``q_rope`` (B, H, rr), ``q_sel`` = their concatenation and ``lat_new``
    (B, D), the token's latent row.  ``wo`` (and MLA's ``wv_b``) come out
    too, so the attend dispatch takes a scanned layer's weights from here
    and not from a separate slice."""
    hln = attn_mod.rms_norm(h, blk["ln1"], cfg.norm_eps)
    p = blk["core"]
    if cfg.mla is None:
        q, k_new, v_new = attn_mod._qkv(p, cfg, hln, pos)
        return {"q": q, "q_sel": q[:, 0], "k_new": k_new, "v_new": v_new,
                "wo": p["wo"]}
    # absorbed MLA: the query lives in latent space (q_lat = q_nope @ W_UK
    # ‖ q_rope) and the new token's cache row is ONE latent vector; both
    # selection and attention run over the store's single latent plane
    m = cfg.mla
    q_nope, q_rope = attn_mod._mla_q(p, cfg, hln, pos)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_lat = jnp.einsum("bhd,hrd->bhr", q_nope[:, 0], p["wk_b"]) * scale
    q_rope = q_rope[:, 0] * scale
    kv_a = (hln @ p["wkv_a"])[:, 0]
    ckv_new = attn_mod.rms_norm(kv_a[:, : m.kv_lora_rank], p["kv_norm"],
                                cfg.norm_eps)
    krope_new = attn_mod.rotate(cfg, kv_a[:, None, None, m.kv_lora_rank:],
                                pos)[:, 0, 0]
    return {"q_lat": q_lat, "q_rope": q_rope,
            "q_sel": jnp.concatenate([q_lat, q_rope], axis=-1),
            "lat_new": jnp.concatenate([ckv_new, krope_new], axis=-1),
            "wv_b": p["wv_b"], "wo": p["wo"]}


def _post_attention(cfg: ArchConfig, mlp_kind: str, blk, h, y) -> jax.Array:
    """One attention layer's dense work after the attend: the residual add
    and the MLP (dense or MoE, inference dispatch)."""
    h, _ = lm._apply_mlp(blk, cfg, mlp_kind, h + y, None, no_drop=True)
    return h


def _post_attention_moe(cfg: ArchConfig, blk, h, y, counts):
    """:func:`_post_attention` of an expert layer that also adds its
    dispatch counts (``mlp.MOE_COUNTS``, int32) to the running ``counts``."""
    x = h + y
    out, _, n = mlp_mod.moe_apply(
        blk["mlp"], cfg, attn_mod.rms_norm(x, blk["ln2"], cfg.norm_eps),
        no_drop=True, counts=True)
    return x + out, counts + n


def _layer_fn(cfg: ArchConfig, which: str, stacked: bool, mlp_kind: str):
    """``(weights, r, h, pos | y)`` -> :func:`_pre_attention` (``which=
    "pre"``) or :func:`_post_attention` (``"post"``) of one layer.  With
    ``stacked`` the weights are a body period position's stacked tree and
    ``r`` a traced int32 repeat index: under jit the slice happens inside
    the program, where XLA fuses or elides the copy.  Otherwise the
    weights are a prologue block and ``r`` is None.  The functions' names
    name the programs in a profiler trace (``jit_pre_attention``,
    ``jit_post_attention``, and for an expert layer
    ``jit_post_attention_moe``, which given the round's running dispatch
    counts returns ``(h, counts)``)."""
    def pick(w, r):
        return jax.tree.map(lambda a: a[r], w) if stacked else w

    def pre_attention(w, r, h, pos):
        return _pre_attention(cfg, pick(w, r), h, pos)

    def post_attention(w, r, h, y):
        return _post_attention(cfg, mlp_kind, pick(w, r), h, y)

    def post_attention_moe(w, r, h, y, counts=None):
        if counts is None:
            return post_attention(w, r, h, y)
        return _post_attention_moe(cfg, pick(w, r), h, y, counts)

    if which == "pre":
        return pre_attention
    return post_attention_moe if mlp_kind == "moe" else post_attention


class BatchedLeoAMEngine:
    """Batched tiered-decoding engine over a decoder-only model.

    Sequences join via :meth:`add_sequence` (per-request prefill, as in
    continuous batching), decode together via :meth:`decode_round`, and
    leave via :meth:`release` — the scheduler drives exactly this API.
    """

    def __init__(self, cfg: ArchConfig, params, ecfg: EngineCfg, *,
                 max_seqs: int = 1,
                 device_chunk_budget: Optional[int] = None):
        if cfg.is_encdec:
            raise ValueError(
                f"LeoAMEngine drives decoder-only models; '{cfg.name}' is "
                f"an encoder-decoder architecture — serve it with the "
                f"per-request runtime paths instead")
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.chunk = cfg.leoam.chunk_size
        self.n_chunks = ecfg.max_len // self.chunk
        self.max_seqs = max_seqs
        self.attn_layers = [i for i, k in enumerate(cfg.layer_kinds())
                            if k.startswith("attn")]
        # absorbed-MLA stacks tier ONE latent row per token — concat(ckv,
        # krope), a single logical kv head of width kv_lora_rank +
        # qk_rope_head_dim — through the same store/selection machinery:
        # the LKA box over the concatenated latent IS the MLA bound
        # (q_lat·ckv + q_rope·krope == q_cat·latent), so chunk importance
        # reuses chunk_bounds_gqa_matmul with Hkv=1 unchanged.
        self.mla = cfg.mla is not None
        if ecfg.prefix_cache:
            bad = [k for k in cfg.layer_kinds() if not k.startswith("attn")]
            if bad:
                # recurrent blocks carry decode state OUTSIDE the KV store
                # (mamba/xlstm hidden state), which a by-reference prefix
                # adoption cannot reconstruct — warm resume would be wrong
                raise ValueError(
                    f"prefix_cache requires an attention-only stack; "
                    f"'{cfg.name}' has non-attention layers {sorted(set(bad))} "
                    f"whose recurrent decode state the shared-prefix cache "
                    f"cannot adopt by reference")
            C = ecfg.prefill_chunk_tokens
            if C % self.chunk or ecfg.max_len % C:
                raise ValueError(
                    f"prefix_cache admissions run chunked prefill: "
                    f"prefill_chunk_tokens={C} must be a multiple of the "
                    f"store chunk ({self.chunk}) and divide max_len "
                    f"({ecfg.max_len})")
        if self.mla:
            self.lat_dim = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            kv_heads, kv_dim = 1, self.lat_dim
        else:
            kv_heads, kv_dim = cfg.n_kv_heads, cfg.hd
        budget = (device_chunk_budget * len(self.attn_layers)
                  if device_chunk_budget is not None else None)
        self.store = TieredKVStore(
            len(self.attn_layers), self.n_chunks, self.chunk,
            kv_heads, kv_dim, n_seqs=max_seqs,
            transit_codec=ecfg.transit_codec, device_budget=budget,
            use_pool=ecfg.pooled, pool_slots=device_chunk_budget,
            real_codec=ecfg.real_codec, disk_sidecar=ecfg.disk_sidecar,
            sidecar_lossless=ecfg.sidecar_lossless, latent=self.mla,
            prefix_rows=(max(1, ecfg.prefix_arena_rows)
                         if ecfg.prefix_cache else 0),
            debug_sync=ecfg.debug_sync, checksums=ecfg.checksums,
            faults=ecfg.fault_plan, io_retries=ecfg.io_retries,
            io_backoff_s=ecfg.io_backoff_s,
            abstract_kind=("pq" if ecfg.pq_abstracts else "minmax"),
            pq_m=ecfg.pq_m, pq_centroids=ecfg.pq_centroids,
            pq_train_iters=ecfg.pq_train_iters)
        self.seqs: Dict[int, _SeqState] = {}
        self._free: List[int] = list(range(max_seqs - 1, -1, -1))
        # DTP state: prefetch executor, per-(seq, layer) previous-round
        # selections, per-layer abstract cache, per-layer measured costs
        self._executor = _prefetch_executor() if ecfg.pipeline else None
        self._ingest_exec = (_prefetch_executor() if ecfg.overlap_ingest
                             else None)
        self._pf_futs: Dict[int, Future] = {}
        self._abs_cache: Dict[int, Tuple] = {}
        self._prev_sels: Dict[Tuple[int, int], List[int]] = {}
        self._lcost: Dict[int, Dict[str, float]] = {}
        self.round_profiles: List[Dict[str, float]] = []
        self.admit_profiles: List[Dict[str, float]] = []
        self._prefill_cache: Dict[int, Any] = {}
        self._chunk_prefill_cache: Dict[int, Any] = {}
        # the decode round's compiled dense layer work, keyed by (pre|post,
        # prologue|body, position); a scanned layer's repeat index rides in
        # as one of these device scalars, so a program indexes the stacked
        # weights in place and compiles once per batch size, not per layer
        self._layer_programs: Dict[Tuple[str, str, int], Any] = {}
        self._repeat_idx = [jnp.asarray(r, jnp.int32)
                            for r in range(lm._layer_plan(cfg)[2])]
        # a round's expert layers add their dispatch counts to this
        self._moe_zero = jnp.zeros(len(mlp_mod.MOE_COUNTS), jnp.int32)
        self._round_idx = 0
        # fault domain: per-seq terminal failure reasons (scheduler pops
        # them after each round) + engine-level counters
        self.failed: Dict[int, str] = {}
        self.seqs_failed = 0
        self.ingest_errors = 0
        # overload control: preempted sequences park here ({sid:
        # _SeqState}); they keep their engine slot — the store row holds
        # their only full replica — but release every hot-tier resource
        self.suspended: Dict[int, _SeqState] = {}
        # the last decode round's (V,) f32 logits per sequence (what its
        # token was sampled from) — for checks against a dense reference
        self.last_logits: Dict[int, np.ndarray] = {}

    @property
    def free_slots(self) -> int:
        """Sequence slots available for admission (scheduler-facing)."""
        return len(self._free)

    # ------------------------------------------------------------------
    # Sequence lifecycle
    # ------------------------------------------------------------------
    @decode_thread_only
    def add_sequence(self, tokens: np.ndarray) -> Tuple[int, int]:
        """Prefill one request into a free store slot.

        tokens: (S,).  Runs model prefill; K/V moves into the shared tier
        store under this sequence's slot.  With ``overlap_ingest`` each
        attention layer's K/V is handed to the store as soon as it is
        forced off the device, and the layer's disk replica + abstract
        writes run write-behind on the shared prefetch executor, overlapped
        under the remaining layers' prefill compute; ``decode_round`` and
        ``release`` fence them before any read.  Returns (seq id, first
        token).
        """
        self._check_capacity()
        self._check_prompt(tokens)     # validate BEFORE taking the slot
        sid = self._free.pop()
        self.failed.pop(sid, None)     # the slot starts a fresh lifetime
        try:
            return self._admit(sid, tokens, pool_place=True)
        except BaseException:
            # a failed synchronous admission must not leak the slot —
            # drain whatever the partial prefill already queued and
            # recycle before re-raising to the caller
            self.abort_admission(sid)
            raise

    @decode_thread_only
    def add_sequence_async(self, tokens: np.ndarray) -> Future:
        """Admission under decode: reserve a slot NOW, run the prefill +
        ingest on the process-wide admission worker, overlapped with the
        active batch's decode rounds — only the store-mutation critical
        sections serialize (the store lock).  The admitted sequence skips
        initial device-pool placement (the pool slab is read by decode's
        attention gathers outside the lock; the first decode round promotes
        its chunks instead — residency-only, token streams are unchanged).
        Returns a Future resolving to (seq id, first token); the sequence
        may join a decode round only after it resolves."""
        self._check_capacity()
        self._check_prompt(tokens)     # validate BEFORE taking the slot
        sid = self._free.pop()
        self.failed.pop(sid, None)     # the slot starts a fresh lifetime
        return _admit_executor().submit(self._admit_guarded, sid, tokens,
                                        pool_place=False)

    def _check_capacity(self) -> None:
        """Admission-path guard (raises, never asserts: admission requests
        are external input, and ``python -O`` must not admit past
        capacity).  The scheduler checks ``free_slots`` first; a direct
        caller gets an actionable error instead of a slot-leak."""
        if not self._free:
            raise ValueError(
                f"engine is at max_seqs={self.max_seqs} capacity — release "
                f"a sequence first, or rebuild the engine with a larger "
                f"max_seqs (the scheduler gates on engine.free_slots)")

    def _check_prompt(self, tokens: np.ndarray) -> None:
        """Reject oversized prompts before a slot is reserved — raising
        after the ``_free.pop()`` would leak the slot."""
        S = len(tokens)
        if S >= self.ecfg.max_len:
            raise ValueError(
                f"prompt length {S} needs < max_len={self.ecfg.max_len} "
                f"(decode appends past the prompt); raise EngineCfg.max_len "
                f"or truncate the prompt")

    @worker_thread
    def _admit_guarded(self, sid: int, tokens: np.ndarray, *,
                       pool_place: bool) -> Tuple[int, int]:
        """Admission-worker wrapper: any failure surfaces as a typed
        :class:`AdmissionError` carrying the slot id, so the scheduler
        (decode thread) can reclaim exactly that slot via
        :meth:`abort_admission` — the worker itself must not mutate the
        free list (slot recycling is decode-thread-owned)."""
        try:
            return self._admit(sid, tokens, pool_place=pool_place)
        except BaseException as e:
            raise AdmissionError(sid, e) from e

    @worker_thread
    def _admit(self, sid: int, tokens: np.ndarray, *,
               pool_place: bool) -> Tuple[int, int]:
        if self.ecfg.prefix_cache:
            # content-addressable admission always runs the chunked-prefill
            # path: a warm prefix loads the shared span's KV straight into
            # the cache and prefill resumes at the cold suffix — whole-
            # prompt prefill has no way to skip the matched span
            adm = ChunkedAdmission(self, sid, np.asarray(tokens),
                                   self.ecfg.prefill_chunk_tokens,
                                   pool_place=pool_place)
            while not adm.done:
                adm._step_impl()
            return adm.result
        cfg, ecfg = self.cfg, self.ecfg
        S = len(tokens)
        t0 = time.perf_counter()
        logits, cache = self._prefill(np.asarray(tokens))

        placement = self._default_placement()
        prefill_s = ingest_s = 0.0
        if self._ingest_exec is None:
            # serial path (PR-2): force the whole prefill, then ingest and
            # write every layer's replicas inline — the A/B baseline the
            # fig13 TTFT breakdown measures the tier-write stall against
            cache = jax.tree.map(np.asarray, cache)
            prefill_s = time.perf_counter() - t0
            t1 = time.perf_counter()
            for li, layer in enumerate(self.attn_layers):
                k, v = self._layer_kv(cache, layer)
                self.store.ingest(li, k[0], v[0],
                                  self._layer_placement(layer, placement),
                                  seq=sid, pool_place=pool_place)
            ingest_s = time.perf_counter() - t1
        else:
            # layer-streamed: force each attention layer's K/V in layer
            # order and hand it off immediately — the hot placement is
            # synchronous, the replica/abstract writes go write-behind on
            # the shared executor while later layers still compute
            for li, layer in enumerate(self.attn_layers):
                k, v = self._layer_kv(cache, layer)
                t1 = time.perf_counter()
                self.store.ingest(li, k[0], v[0],
                                  self._layer_placement(layer, placement),
                                  seq=sid, executor=self._ingest_exec,
                                  pool_place=pool_place)
                ingest_s += time.perf_counter() - t1
            cache = jax.tree.map(np.asarray, cache)
            prefill_s = time.perf_counter() - t0 - ingest_s
        tok = int(np.argmax(np.asarray(logits)[0]))
        self.seqs[sid] = _SeqState(cache=cache, length=S,
                                   access=AccessTable(self.n_chunks),
                                   tokens=np.asarray(tokens), prompt_len=S)
        self.admit_profiles.append({
            "total_s": time.perf_counter() - t0, "prefill_s": prefill_s,
            "ingest_s": ingest_s,
            "overlapped": float(self._ingest_exec is not None)})
        return sid, tok

    def _default_placement(self) -> Dict[int, str]:
        """Admission tier placement by chunk index (device head, host
        middle, disk tail)."""
        ecfg = self.ecfg
        n_gpu = max(1, int(self.n_chunks * ecfg.gpu_chunk_frac))
        n_cpu = max(1, int(self.n_chunks * ecfg.cpu_chunk_frac))
        return {c: DEVICE if c < n_gpu else
                (HOST if c < n_gpu + n_cpu else DISK)
                for c in range(self.n_chunks)}

    def _bucket_len(self, S: int) -> int:
        """Smallest bucket >= S: powers of two from 16, or the configured
        ``prefill_buckets`` schedule, capped at max_len (the cache pad)."""
        sched = self.ecfg.prefill_buckets
        if sched:
            for b in sorted(sched):
                if b >= S:
                    return min(int(b), self.ecfg.max_len)
            return self.ecfg.max_len
        b = 16
        while b < S:
            b <<= 1
        return min(b, self.ecfg.max_len)

    @property
    def prefill_programs(self) -> int:
        """Distinct compiled prefill programs (bucketed whole-prompt +
        chunk-step).  With ``bucket_prefill`` this stays O(log max_len)
        under ANY prompt-length distribution — the mixed-length bench and
        the CI baseline gate watch this counter."""
        return len(self._prefill_cache) + len(self._chunk_prefill_cache)

    def _prefill(self, tokens: np.ndarray):
        """Model prefill, jit-compiled per LENGTH BUCKET: the prompt is
        right-padded to the bucket and the true length rides in as a traced
        scalar (logits row, cache zeroing and recurrent-state masking all
        honor it — token-identical to exact-length prefill, tested), so
        ceil(log2(max_len))-ish programs serve any public-traffic length
        mix instead of one compile per distinct length.  One XLA call
        replaces thousands of eager op dispatches: admission cost drops
        several-fold, and the GIL is free for the decode thread while an
        async admission's prefill executes."""
        S = len(tokens)
        if not self.ecfg.jit_prefill:
            batch = {"tokens": jnp.asarray(np.asarray(tokens)[None],
                                           jnp.int32)}
            return lm.prefill(self.params, self.cfg, batch,
                              max_len=self.ecfg.max_len)
        cfg, max_len = self.cfg, self.ecfg.max_len
        if self.ecfg.bucket_prefill:
            B = self._bucket_len(S)
            padded = np.zeros(B, np.int64)
            padded[:S] = np.asarray(tokens)
            batch = {"tokens": jnp.asarray(padded[None], jnp.int32),
                     "length": jnp.int32(S)}
            key = B
        else:
            batch = {"tokens": jnp.asarray(np.asarray(tokens)[None],
                                           jnp.int32)}
            key = S
        fn = self._prefill_cache.get(key)
        if fn is None:
            fn = jax.jit(lambda p, b: lm.prefill(p, cfg, b, max_len=max_len))
            self._prefill_cache[key] = fn
        return fn(self.params, batch)

    def _prefill_chunk(self, batch: Dict[str, Any], cache):
        """One jitted chunked-prefill step; compiled once per chunk size
        (the cache is donated so XLA updates it in place)."""
        C = batch["tokens"].shape[1]
        fn = self._chunk_prefill_cache.get(C)
        if fn is None:
            cfg, max_len = self.cfg, self.ecfg.max_len
            fn = jax.jit(
                lambda p, b, c: lm.prefill_chunk(p, cfg, b, c,
                                                 max_len=max_len),
                donate_argnums=(2,))
            self._chunk_prefill_cache[C] = fn
        return fn(self.params, batch, cache)

    def _layer_program(self, which: str, where: str, pi: int, mlp_kind: str):
        """The jitted :func:`_layer_fn` of prologue layer ``pi`` or body
        period position ``pi`` (``where``), built once per engine; JAX's
        own cache then keys it on the batch size."""
        key = (which, where, pi)
        fn = self._layer_programs.get(key)
        if fn is None:
            fn = self._layer_programs[key] = jax.jit(
                _layer_fn(self.cfg, which, where == "body", mlp_kind))
        return fn

    @decode_thread_only
    def begin_admission(self, tokens: np.ndarray, *,
                        chunk_tokens: Optional[int] = None,
                        pool_place: bool = True) -> "ChunkedAdmission":
        """Start a CHUNKED admission: reserves the slot now and returns a
        resumable :class:`ChunkedAdmission` whose ``step()`` forces one
        fixed-size prefill chunk through the cache and streams its K/V into
        the tier store (write-behind cold half unchanged), yielding between
        chunks so the caller can run decode rounds in the gaps — a very
        long prompt no longer stalls the round loop for its whole prefill.
        Intended to be stepped on the decode thread (the scheduler's
        chunked-admission mode); ``pool_place=False`` defers device-pool
        placement exactly like ``add_sequence_async``.  Drives GQA and
        absorbed-MLA stacks alike (MLA chunks stream latent rows through
        the store's single-plane layout)."""
        C = chunk_tokens or self.ecfg.prefill_chunk_tokens
        if C % self.chunk or self.ecfg.max_len % C:
            raise ValueError(
                f"prefill chunk_tokens={C} must be a multiple of the store "
                f"chunk ({self.chunk}) and divide max_len "
                f"({self.ecfg.max_len}) so partial ingests stay "
                f"chunk-aligned")
        self._check_capacity()
        self._check_prompt(tokens)     # validate BEFORE taking the slot
        sid = self._free.pop()
        self.failed.pop(sid, None)     # the slot starts a fresh lifetime
        return ChunkedAdmission(self, sid, tokens, C, pool_place=pool_place)

    _KV_LEAVES = ("k", "v", "ckv", "krope")

    def _layer_cache(self, cache, layer: int) -> Dict[str, Any]:
        """The KV/latent leaves of one layer's attention cache (body
        layers sliced out of their stacked repeat axis; pyramid leaves are
        engine-unused, so they are not materialized)."""
        pro_n = len(cache["prologue"])
        if layer < pro_n:
            return cache["prologue"][layer]
        period = self.cfg.period()
        bi = (layer - pro_n) // period
        pi = (layer - pro_n) % period
        return {k: v[bi] for k, v in cache["body"][pi].items()
                if k in self._KV_LEAVES}

    def _layer_kv_slice(self, cache, layer: int, start: int, n: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
        """Like :meth:`_layer_kv` but pulls only rows [start, start+n) to
        the host — the chunked-admission stream-out.  MLA layers return
        the latent rows (concat(ckv, krope), a single kv head) in both
        positions."""
        c = self._layer_cache(cache, layer)
        sl = lambda a: np.asarray(
            jax.lax.dynamic_slice_in_dim(a, start, n, axis=1))[0]
        if self.mla:
            lat = np.concatenate([sl(c["ckv"]), sl(c["krope"])],
                                 axis=-1)[:, None, :]
            return lat, lat
        return sl(c["k"]), sl(c["v"])

    def _layer_placement(self, layer: int,
                         placement: Dict[int, str]) -> Dict[int, str]:
        if layer < self.cfg.leoam.early_layers:
            # early layers never go to disk (§4.3)
            return {c: (DEVICE if placement[c] == DEVICE else HOST)
                    for c in placement}
        return dict(placement)

    @decode_thread_only
    def release(self, sid: int) -> None:
        """Retire a sequence and recycle its store slot.

        Drains every in-flight future that may still reference the slot —
        write-behind ingest writes (per-seq fence), the DTP prefetch
        worker's staged reads, and queued sidecar repacks — BEFORE clearing
        the store, so a slow replica write can never land in a recycled
        slot's fresh data (and a queued repack completes deterministically
        instead of being aborted by the slot's version bump).

        Exception-safe: a raised cold-ingest future (the fence drains ALL
        of the seq's futures before surfacing the first failure), a failed
        prefetch, or a failed repack is counted but swallowed — the
        sequence is being retired, so the store teardown and slot recycle
        ALWAYS run; the slot can never leak and the fence can never stay
        poisoned for the next admission."""
        self._drain_seq(sid)
        self._abs_cache.clear()
        self.store.clear_seq(sid)
        self.seqs.pop(sid, None)
        self.suspended.pop(sid, None)
        for key in [k for k in self._prev_sels if k[0] == sid]:
            self._prev_sels.pop(key, None)
        if sid not in self._free:
            self._free.append(sid)

    def _drain_seq(self, sid: int) -> None:
        """Best-effort drain of every in-flight future that may reference
        a slot (ingest fence, prefetch worker, repack queue).  Failures
        are counted, never raised: every teardown path (release /
        abort_admission / fail_sequence) must run to completion."""
        try:
            self.store.ingest_fence(sid)
        except BaseException:
            self.ingest_errors += 1
        for li in list(self._pf_futs):
            fut = self._pf_futs.pop(li, None)
            if fut is not None:
                try:
                    fut.result()
                except BaseException:
                    pass
        try:
            self.store.requant_fence()
        except BaseException:
            pass

    @decode_thread_only
    def abort_admission(self, sid: int) -> None:
        """Reclaim a slot whose admission failed or was cancelled
        mid-flight (the decode-thread half of :class:`AdmissionError`
        handling, and the teardown for a deadline-cancelled
        :class:`ChunkedAdmission`).

        Drains the slot's write-behind ingest futures (their failure is
        the reason we are here — swallowed), then releases everything the
        partial admission may hold: pool slots and deferred placements,
        prefix-arena refcounts including the unpublished registration
        plan, tier entries, and the per-slot traffic log — before
        recycling the slot.  Idempotent."""
        self._drain_seq(sid)
        self.store.clear_seq(sid)
        self.seqs.pop(sid, None)
        self.suspended.pop(sid, None)
        for key in [k for k in self._prev_sels if k[0] == sid]:
            self._prev_sels.pop(key, None)
        if sid not in self._free:
            self._free.append(sid)

    @decode_thread_only
    def fail_sequence(self, sid: int, reason: str) -> None:
        """Contain ONE sequence's failure as its terminal state.

        Tears the sequence down exactly like :meth:`release` (drain,
        clear, recycle) and records the reason in :attr:`failed` for the
        scheduler to surface — no other sequence's state is touched, so
        their decode streams stay token-identical (chaos-tested)."""
        self._drain_seq(sid)
        self._abs_cache.clear()
        self.store.clear_seq(sid)
        self.seqs.pop(sid, None)
        self.suspended.pop(sid, None)
        for key in [k for k in self._prev_sels if k[0] == sid]:
            self._prev_sels.pop(key, None)
        if sid not in self._free:
            self._free.append(sid)
        self.failed[sid] = reason
        self.seqs_failed += 1

    # ------------------------------------------------------------------
    # Whole-sequence preemption (overload control)
    # ------------------------------------------------------------------
    @decode_thread_only
    def suspend_sequence(self, sid: int) -> None:
        """Preempt ONE live sequence: fence its write-behind ingest, drop
        its speculative prefetch state, swap its entire hot working set
        down to the disk tier (pool slots, host copies and prefix-arena
        refs all released — :meth:`TieredKVStore.swap_out_seq`), and park
        its decode state in :attr:`suspended`.

        The engine slot stays reserved — the victim's only full replica
        lives in that store row — so preemption relieves pool slots, host
        bytes, and the scheduler's batch seat, never ``free_slots``.
        Transparency (I7): the host-side ``_SeqState`` (model cache,
        access counts, prompt tokens) is preserved untouched, the store's
        access/abstract/CRC state is NOT cleared, and the write-through
        replica already holds every appended row — so suspend + resume is
        the identity on the token stream (property-tested)."""
        if sid not in self.seqs:
            raise KeyError(f"suspend_sequence: seq {sid} is not live "
                           f"(live={sorted(self.seqs)})")
        self._drain_seq(sid)
        self._abs_cache.clear()
        for key in [k for k in self._prev_sels if k[0] == sid]:
            self._prev_sels.pop(key, None)
        st = self.seqs.pop(sid)
        self.store.swap_out_seq(sid)
        self.suspended[sid] = st

    @decode_thread_only
    def resume_sequence(self, sid: int) -> None:
        """Un-park a suspended sequence: re-stage its remembered host
        working set from the disk replicas (``swap_in_seq``; a chunk that
        fails verification degrades to the engine's usual disk-lost
        recovery on its next fetch) and rejoin the live set — the next
        decode round continues bitwise where the victim left off."""
        st = self.suspended.pop(sid, None)
        if st is None:
            raise KeyError(f"resume_sequence: seq {sid} is not suspended "
                           f"(suspended={sorted(self.suspended)})")
        self.store.swap_in_seq(sid)
        self.seqs[sid] = st

    def fault_stats(self) -> Dict[str, float]:
        """Engine + store fault-domain counters (scheduler/audit-facing)."""
        out = self.store.fault_stats()
        out["seqs_failed"] = float(self.seqs_failed)
        out["ingest_errors"] = float(self.ingest_errors)
        return out

    def pool_stats(self) -> Dict[str, float]:
        """Live device-pool occupancy/hit counters (scheduler-facing)."""
        return self.store.pool_stats()

    def admission_need_chunks(self, prompt_len: int, max_new: int) -> int:
        """Worst-case per-round device working set of one request, in pool
        slots per layer — what pool-aware admission charges a sequence
        (far below the analytic ``max_len``-chunks worst case)."""
        cfg, ecfg = self.cfg, self.ecfg
        L = min(prompt_len + max_new, ecfg.max_len)
        nv = -(-L // self.chunk)
        rate = max(cfg.leoam.importance_rate, cfg.leoam.early_rate)
        sel = -(-max(self.chunk, math.ceil(L * rate)) // self.chunk)
        forced = (cfg.leoam.sink_chunks + cfg.leoam.recent_chunks
                  + math.ceil(ecfg.hot_frac * nv))
        return min(nv, sel + forced)

    def _layer_kv(self, cache, layer: int) -> Tuple[np.ndarray, np.ndarray]:
        """Pull (k, v) (B, S, Hkv, hd) for a layer out of a model cache.
        MLA layers yield the latent rows (B, S, 1, r + rr) in both
        positions (the store keeps a single latent plane)."""
        c = self._layer_cache(cache, layer)
        if self.mla:
            lat = np.concatenate([np.asarray(c["ckv"]),
                                  np.asarray(c["krope"])],
                                 axis=-1)[:, :, None, :]
            return lat, lat
        return np.asarray(c["k"]), np.asarray(c["v"])

    # ------------------------------------------------------------------
    # DTP: measured-cost θ balance + speculative prefetch
    # ------------------------------------------------------------------
    def _theta(self, li: int) -> float:
        """Per-layer compressed fraction of the upload delta (§4.4): the
        smallest θ hiding the transfer under the measured compute window."""
        if not (self.ecfg.real_codec and self.ecfg.transit_codec):
            return 1.0
        lc = self._lcost.get(li)
        if lc is None:
            return 1.0                 # no measurement yet: compress all
        bw = dtp.TierBW(pcie=self.ecfg.pcie_bw, disk=self.ecfg.disk_bw,
                        kappa=self.ecfg.kappa,
                        delta=compression.codec_ratio(self.ecfg.transit_codec,
                                                      group=self.chunk))
        return dtp.theta_from_measured(lc["D"], lc["T0"], lc["Tc"], bw)

    def _update_costs(self, li: int, upload_bytes: float, disk_bytes: float,
                      compute_s: float) -> None:
        """EMA of the layer's measured round costs.  Without ``profile``
        the compute window is (round − host stages)/n_attn — an UPPER
        bound that also amortizes MLP/recurrent layers over the attention
        layers, so θ errs toward less compression; run with
        ``profile=True`` for the per-dispatch-blocked exact window."""
        lc = self._lcost.setdefault(li, {"D": upload_bytes, "T0": disk_bytes,
                                         "Tc": max(compute_s, 1e-7)})
        for k, v in (("D", upload_bytes), ("T0", disk_bytes),
                     ("Tc", max(compute_s, 1e-7))):
            lc[k] = 0.5 * lc[k] + 0.5 * v

    def _submit_prefetch(self, li: int, order: Sequence[int],
                         lengths: np.ndarray) -> None:
        """Overlap layer ``li``'s abstract reads + speculative disk staging
        under the previous layer's attention.  Predictions come from the
        previous round's selection for (seq, li), else the AccessTable hot
        set — residency-only, so a miss can never change outputs.

        The thread hop only pays for itself when there is disk latency to
        hide, so the submit is adaptive: once the predicted working set is
        fully above the disk tier (steady state on a warm pool) the layer
        is handled inline and the worker stays idle."""
        if self._executor is None or li >= len(self.attn_layers) \
                or li in self._pf_futs:
            return
        chunks_by_seq = {}
        pred = {}
        any_disk = False
        for i, sid in enumerate(order):
            nv = (int(lengths[i]) + self.chunk - 1) // self.chunk
            chunks_by_seq[sid] = list(range(nv))
            prev = self._prev_sels.get((sid, li))
            if prev is None:
                prev = [int(c) for c in
                        self.seqs[sid].access.hot_tokens(self.ecfg.hot_frac)]
            pred[sid] = [c for c in prev if c < nv]
            tiers = self.store.tier_view(sid, li) \
                if self.ecfg.prefix_cache else self.store.tier[sid, li]
            if not any_disk and any(tiers[c] == DISK for c in pred[sid]):
                any_disk = True
        if not any_disk:
            return
        key = tuple((sid, len(chunks_by_seq[sid])) for sid in order)

        @worker_thread
        def work():
            res = (self.store.read_abstracts_pq_batch(li, chunks_by_seq)
                   if self.ecfg.pq_abstracts
                   else self.store.read_abstracts_batch(li, chunks_by_seq))
            self._abs_cache[li] = (key, res)
            self.store.stage_host(li, pred)

        self._pf_futs[li] = self._executor.submit(work)

    # ------------------------------------------------------------------
    # Importance evaluation (batched LKA + per-sequence IAKM)
    # ------------------------------------------------------------------
    def _select_chunks_batched(self, li: int, layer: int, q: np.ndarray,
                               order: Sequence[int], lengths: np.ndarray
                               ) -> Tuple[Dict[int, List[int]],
                                          Dict[int, StepStats]]:
        """One bounds matmul over the stacked batch, then per-sequence
        chunk-level adaptive selection (tree/IAKM or flat) on the host.

        q: (B, H, d) PRE-SCALED queries, rows matching ``order`` — GQA
        passes q/sqrt(hd) against the per-head key boxes; MLA passes
        concat(q_lat, q_rope)·scale against the latent boxes (Hkv=1), for
        which the same positive/negative-split matmul bound is exact.
        """
        cfg = self.cfg
        chunk = self.chunk
        n_valid = {sid: (int(L) + chunk - 1) // chunk
                   for sid, L in zip(order, lengths)}
        chunks_by_seq = {sid: list(range(n_valid[sid])) for sid in order}
        use_pq = self.ecfg.pq_abstracts
        with span("leoam.select.abstracts"):
            fut = self._pf_futs.pop(li, None)
            if fut is not None:
                fut.result()
            cached = self._abs_cache.pop(li, None)
            key = tuple((sid, n_valid[sid]) for sid in order)
            if cached is not None and cached[0] == key:
                res = cached[1]
            else:   # speculation miss (round composition changed): sync
                    # read.  The worker's read stays billed — two reads
                    # really happened; that is the cost of a wrong
                    # speculation.
                res = (self.store.read_abstracts_pq_batch(li, chunks_by_seq)
                       if use_pq
                       else self.store.read_abstracts_batch(li,
                                                            chunks_by_seq))
            if use_pq:
                km, kn, pq_codes, pq_valid, pq_cb, abs_billed = res
            else:
                km, kn, abs_billed = res
                pq_valid = None
            qj = jnp.asarray(q)                              # (B, H, d)
            kmj, knj = jnp.asarray(km), jnp.asarray(kn)

        with span("leoam.select.bounds"):
            ub, _ = chunk_bounds_gqa_matmul(qj, kmj, knj)
            with span("leoam.sync"):
                ub = np.asarray(ub)                          # (B, Hkv, ncmax)
            adc = None
            if use_pq and pq_valid.any():
                # asymmetric-distance scores off the PQ codes: the
                # exact-logit analog of the bounds path's group sum — q
                # summed per kv group against decoded centroids, max over
                # a chunk's live tokens.  Only code-valid chunks use it;
                # the rest keep the min/max upper bound BITWISE (np.where
                # below selects whole values, never mixes them).
                B, H = q.shape[0], q.shape[1]
                Hkv = km.shape[2]
                q_sum = q.reshape(B, Hkv, H // Hkv, -1).sum(2)  # (B, Hkv, d)
                adc = adc_chunk_scores(q_sum, pq_cb, pq_codes,
                                       np.asarray(lengths))  # (B, Hkv, nc)
        with span("leoam.select.choose"):
            rate = (cfg.leoam.early_rate if layer < cfg.leoam.early_layers
                    else cfg.leoam.importance_rate)
            sels: Dict[int, List[int]] = {}
            stats: Dict[int, StepStats] = {}
            for i, sid in enumerate(order):
                st = StepStats(abstract_bytes=abs_billed[sid])
                nv = n_valid[sid]
                length = int(lengths[i])
                scores = ub[i].max(0)[:nv]                       # (nv,)
                if adc is not None:
                    v = pq_valid[i, :nv]
                    scores = np.where(v, adc[i].max(0)[:nv], scores)
                budget_tokens = max(chunk, int(math.ceil(length * rate)))
                # chunk-level fast path: equivalent to the per-token
                # repeat+select (tested) without the length-S allocation
                chunk_scores = scores / chunk
                if self.ecfg.selection == "tree":
                    sel, st.evaluations = tree_select_chunks(
                        chunk_scores, length, budget_tokens, chunk)
                else:
                    sel, st.evaluations = flat_select_chunks(
                        chunk_scores, length, budget_tokens, chunk)
                # sink + recent + hot chunks always included
                forced = set(range(cfg.leoam.sink_chunks))
                forced.update(range(max(0, nv - cfg.leoam.recent_chunks), nv))
                forced.update(
                    int(c) for c in self.seqs[sid].access.hot_tokens(
                        self.ecfg.hot_frac) if c < nv)
                sels[sid] = sorted(set(sel) | forced)
                stats[sid] = st
        return sels, stats

    # ------------------------------------------------------------------
    # Decode round
    # ------------------------------------------------------------------
    # decode_round is allowed this many ChunkLostError recoveries before
    # giving up — each recovery either restores chunks or removes a
    # sequence, so a loop that reaches the bound indicates a live fault
    # injector scheduling pathological back-to-back losses
    _MAX_ROUND_RETRIES = 8

    @decode_thread_only
    def decode_round(self, tokens: Dict[int, int]) -> Dict[int, int]:
        """One token for every sequence in ``tokens`` ({seq id: last token}).

        Per attention layer: batched importance eval, one delta promotion
        into the device pool (or one legacy coalesced gather), one padded
        attention dispatch; with ``pipeline`` the next layer's reads run
        under this layer's attention.  Non-attention (recurrent / dense)
        layers keep their exact per-sequence decode path.  Returns
        {seq id: next token}.

        FAILURE CONTAINMENT (I6): a failure on one sequence never takes
        the batch down.  A raised cold-ingest fence fails just that
        sequence (terminal state in :attr:`failed`); a disk-lost chunk
        (:class:`ChunkLostError` from a checksum mismatch or exhausted
        retries) triggers recompute-from-prompt of exactly the affected
        span when it lies inside the prompt (bitwise-identical chunked
        prefill), else fails the owning sequence — and the round retries
        with the survivors, whose streams stay token-identical (batched
        attention is FP-exact w.r.t. batch composition).  Returns {} when
        every sequence failed; the scheduler pops :attr:`failed`.
        """
        if not tokens:
            raise ValueError(
                "decode_round needs at least one sequence: pass "
                "{seq id: last token} for every live sequence (admit one "
                "via add_sequence / add_sequence_async first)")
        live = dict(tokens)
        n_prof = len(self.round_profiles)
        with Round() as rnd:
            with span("leoam.fence"):
                for sid in sorted(live):    # write-behind completion fence:
                    try:                    # no read sees a half-written
                        self.store.ingest_fence(sid)          # replica
                    except BaseException as e:
                        self.ingest_errors += 1
                        self.fail_sequence(sid, f"cold ingest failed: {e!r}")
                        live.pop(sid)
            for _ in range(self._MAX_ROUND_RETRIES):
                if not live:
                    return {}
                snap = self._snapshot_round(live)
                try:
                    out = self._decode_round_impl(live)
                    break
                except ChunkLostError as e:
                    self._restore_round(snap)
                    self._recover_lost(e, live)
            else:
                raise RuntimeError(
                    f"decode round failed to converge after "
                    f"{self._MAX_ROUND_RETRIES} chunk-loss recoveries — the "
                    f"disk is losing chunks faster than recompute restores "
                    f"them")
        # the round's span self times and compiles join its profile (the
        # phases of a retried round include its failed attempts)
        self.round_profiles[n_prof].update(rnd.profile())
        return out

    def _snapshot_round(self, live: Dict[int, int]) -> Dict[str, Any]:
        """Capture the host-side state a partial round mutates before its
        first dispatch can raise, so a retry re-runs from a clean slate.
        Device/pool residency and store billing need no rollback: both
        are value-neutral (residency moves bytes, never values; a retried
        read honestly re-bills)."""
        return {
            "access": {sid: self.seqs[sid].access.counts.copy()
                       for sid in live},
            "prev_sels": dict(self._prev_sels),
        }

    def _restore_round(self, snap: Dict[str, Any]) -> None:
        """Roll back the selection state a failed round half-mutated and
        drop its speculative prefetch (the futures may hold stale layer
        predictions — and one may carry the same ChunkLostError)."""
        for sid, counts in snap["access"].items():
            if sid in self.seqs:
                self.seqs[sid].access.counts[:] = counts
        self._prev_sels.clear()
        self._prev_sels.update(snap["prev_sels"])
        for li in list(self._pf_futs):
            fut = self._pf_futs.pop(li, None)
            if fut is not None:
                try:
                    fut.result()
                except BaseException:
                    pass
        self._abs_cache.clear()

    def _recover_lost(self, e: ChunkLostError,
                      live: Dict[int, int]) -> None:
        """Handle one ChunkLostError: recompute every affected sequence
        whose lost chunks all lie inside its prompt span; fail the rest.

        Recompute covers ALL of a sequence's currently-lost chunks (the
        store's ``disk_lost_keys``), not just the ones this particular
        gather tripped on — one chunked-prefill replay restores the whole
        span."""
        by_seq: Dict[int, set] = {}
        for seq, _p, c in e.keys:
            by_seq.setdefault(seq, set()).add(c)
        lost_all = self.store.disk_lost_keys()
        for sid, cs in by_seq.items():
            if sid not in live:
                continue
            # fold in every OTHER chunk the store currently marks lost for
            # this sequence (a speculative prefetch may have found more):
            # one prefill replay restores the whole set
            cs = cs | {c for (p, _li, c) in lost_all
                       if self.store._phys(sid, c) == p}
            s = self.seqs.get(sid)
            recomputable = (
                s is not None and s.tokens is not None
                and all(min((c + 1) * self.chunk, s.length) <= s.prompt_len
                        for c in cs))
            if not recomputable:
                # the lost span includes decode appends (or the prompt is
                # gone): the KV exists nowhere else — terminal for this
                # sequence, invisible to every other one
                self.fail_sequence(
                    sid, f"disk-lost chunks {sorted(cs)} at layer "
                         f"{e.layer} not recomputable from prompt")
                live.pop(sid)
                continue
            self._recompute_chunks(sid, cs)

    def _recompute_chunks(self, sid: int, cs: List[int]) -> None:
        """Recompute-from-prompt for one sequence's disk-lost prompt-span
        chunks: replay the PR-4 chunked prefill (bitwise-identical to the
        original admission) through the last lost chunk and re-land every
        (layer, chunk) the store still marks lost via
        :meth:`TieredKVStore.restore_chunk` — replica, abstract and CRC
        rebuilt; the quarantined sidecar repacks lazily."""
        s = self.seqs[sid]
        toks = np.asarray(s.tokens)
        C = self.ecfg.prefill_chunk_tokens
        end = min(len(toks), (max(cs) + 1) * self.chunk)
        end = min(-(-end // C) * C, self.ecfg.max_len)
        cache = lm.init_decode_cache(self.cfg, 1, self.ecfg.max_len)
        pos = 0
        while pos < end:
            chunk_toks = np.zeros(C, np.int64)
            take = min(C, len(toks) - pos)
            if take > 0:
                chunk_toks[:take] = toks[pos:pos + take]
            batch = {"tokens": jnp.asarray(chunk_toks[None], jnp.int32),
                     "start": jnp.int32(pos),
                     "length": jnp.int32(len(toks))}
            _, cache = self._prefill_chunk(batch, cache)
            pos += C
        lost_now = self.store.disk_lost_keys()
        for li, layer in enumerate(self.attn_layers):
            for c in sorted(set(cs)):
                if (self.store._phys(sid, c), li, c) not in lost_now:
                    continue
                k, v = self._layer_kv_slice(cache, layer, c * self.chunk,
                                            self.chunk)
                self.store.restore_chunk(li, sid, c, k, v)

    @decode_thread_only
    def _decode_round_impl(self, tokens: Dict[int, int]) -> Dict[int, int]:
        """The round body (see :meth:`decode_round`); every sequence in
        ``tokens`` is live and fenced.  Raises :class:`ChunkLostError`
        for the wrapper's recovery loop."""
        cfg, ecfg = self.cfg, self.ecfg
        order = sorted(tokens)
        B = len(order)
        states = [self.seqs[sid] for sid in order]
        lengths = np.array([s.length for s in states], np.int64)
        x = jnp.asarray([[tokens[sid]] for sid in order], jnp.int32)
        params = self.params
        h = jnp.take(params["embed"], x, axis=0)             # (B, 1, d)

        prologue, period, repeats = lm._layer_plan(cfg)
        round_stats = {sid: StepStats() for sid in order}
        prof = {"eval_s": 0.0, "gather_s": 0.0, "upload_s": 0.0,
                "attend_s": 0.0}
        layer_io: List[Tuple[int, float, float]] = []  # (li, upB, diskB)
        t_round = time.perf_counter()
        li = 0
        new_caches = [{"prologue": list(s.cache["prologue"]),
                       "body": list(s.cache["body"])} for s in states]
        pos = jnp.asarray(lengths[:, None], jnp.int32)       # (B, 1)
        lengths_j = jnp.asarray(lengths.astype(np.int32))    # (B,)
        # the expert layers' dispatch counts, summed on device
        moe_counts = self._moe_zero

        def run_attn(w, r, where, pi, mlpk, h, layer_idx):
            """One attention layer; ``w``/``r``/``where``/``pi`` pick its
            programs' weights (see :meth:`_layer_program`)."""
            nonlocal li, moe_counts
            with span("leoam.qkv"):
                a = self._layer_program("pre", where, pi, mlpk)(w, r, h, pos)
            with span("leoam.sync"):
                qn = np.asarray(a["q_sel"])
            if not self.mla:
                qn = qn / math.sqrt(cfg.hd)
            t0 = time.perf_counter()
            sels, sel_stats = self._select_chunks_batched(
                li, layer_idx, qn, order, lengths)
            prof["eval_s"] += time.perf_counter() - t0

            nmax = max(len(s) for s in sels.values())
            pad = max(1, ecfg.sel_pad)
            nmax = -(-nmax // pad) * pad

            for i, sid in enumerate(order):
                st = round_stats[sid]
                st.evaluations += sel_stats[sid].evaluations
                st.fetched_chunks += len(sels[sid])
                st.abstract_bytes += sel_stats[sid].abstract_bytes
                self.seqs[sid].access.record(np.asarray(sels[sid]))
                self._prev_sels[(sid, li)] = sels[sid]

            if ecfg.pooled:
                with span("leoam.fetch"):
                    slots, _, fst = self.store.fetch_chunks_pooled(
                        li, sels, pad_to=nmax, theta=self._theta(li))
                prof["gather_s"] += fst.gather_s
                prof["upload_s"] += fst.upload_s
                layer_io.append((li, fst.uploads * self.store.chunk_bytes,
                                 fst.disk_bytes))
                for sid in order:
                    round_stats[sid].fetched_bytes += fst.upload_bytes / B
                # overlap: next layer's reads under this layer's attention
                with span("leoam.prefetch"):
                    self._submit_prefetch(li + 1, order, lengths)
                with span("leoam.attend"):
                    chunk_ids = np.full((B, nmax), -1, np.int32)
                    for i, sid in enumerate(order):
                        chunk_ids[i, :len(sels[sid])] = sels[sid]
                    pool = self.store.pools[li]
                    t1 = time.perf_counter()
                    if self.mla:
                        y = _attend_pooled_mla(
                            a["q_lat"], a["q_rope"], pool.kv,
                            jnp.asarray(slots), jnp.asarray(chunk_ids),
                            lengths_j, a["lat_new"], a["wv_b"], a["wo"])
                    else:
                        y = _attend_pooled(
                            a["q"], pool.kv, jnp.asarray(slots),
                            jnp.asarray(chunk_ids), lengths_j,
                            a["k_new"], a["v_new"], a["wo"],
                            attn_softcap=cfg.attn_softcap)
                    if ecfg.profile:
                        jax.block_until_ready(y)
                        prof["attend_s"] += time.perf_counter() - t1
            else:
                with span("leoam.fetch"):
                    t1 = time.perf_counter()
                    kg, vg, _ = self.store.fetch_chunks_batch(li, sels,
                                                              pad_to=nmax)
                    prof["gather_s"] += time.perf_counter() - t1
                    t1 = time.perf_counter()
                    kgj = jnp.asarray(kg)
                    vgj = kgj if self.mla else jnp.asarray(vg)
                    prof["upload_s"] += time.perf_counter() - t1
                with span("leoam.attend"):
                    # positions per padded slot; sentinel pads fail pos <
                    # len.  Strict mask: the store holds tokens
                    # 0..length-1 here (this round's token rides in
                    # k_new/v_new), so pos == length is an unwritten/stale
                    # row, never attended.
                    S = nmax * self.chunk + 1
                    pos_np = np.full((B, S), np.iinfo(np.int64).max,
                                     np.int64)
                    for i, sid in enumerate(order):
                        sel = np.asarray(sels[sid])
                        p = (sel[:, None] * self.chunk
                             + np.arange(self.chunk)[None]).reshape(-1)
                        pos_np[i, :len(p)] = p
                    valid_np = pos_np < lengths[:, None]
                    valid_np[:, -1] = True           # the new token's column
                    valid = jnp.asarray(valid_np)[:, None, None]
                    t1 = time.perf_counter()
                    if self.mla:
                        y = _attend_workingset_mla(
                            a["q_lat"], a["q_rope"], kgj, a["lat_new"],
                            valid, a["wv_b"], a["wo"])
                    else:
                        y = _attend_workingset(
                            a["q"], kgj, vgj, a["k_new"], a["v_new"], valid,
                            a["wo"], attn_softcap=cfg.attn_softcap)
                    if ecfg.profile:
                        jax.block_until_ready(y)
                        prof["attend_s"] += time.perf_counter() - t1
            with span("leoam.sync"):
                # the token axis (size 1) drops on the host, not the device
                if self.mla:
                    kn_np = np.asarray(a["lat_new"])[:, None, :]  # (B, 1, D)
                    vn_np = None
                else:
                    kn_np = np.asarray(a["k_new"])[:, 0]     # (B, Hkv, hd)
                    vn_np = np.asarray(a["v_new"])[:, 0]
            with span("leoam.append"):
                self.store.append_tokens_batch(li, lengths, kn_np, vn_np,
                                               seqs=order)
            li += 1
            with span("leoam.mlp"):
                post = self._layer_program("post", where, pi, mlpk)
                if mlpk != "moe":
                    return post(w, r, h, y)
                h, moe_counts = post(w, r, h, y, moe_counts)
            return h

        def run_other(blk, kind, mlpk, h, layer_idx, cache_slices):
            """Recurrent/dense layers: exact per-sequence standard decode."""
            rows, new_slices = [], []
            for i, cs in enumerate(cache_slices):
                hi, c2, _ = lm._block_decode(blk, cfg, kind, mlpk, h[i:i + 1],
                                             cs, jnp.int32(int(lengths[i])),
                                             layer_idx=layer_idx,
                                             ctx=attn_mod.LOCAL_CTX)
                rows.append(hi)
                new_slices.append(c2)
            return jnp.concatenate(rows, axis=0), new_slices

        for pi, (idx, kind, mlpk) in enumerate(prologue):
            blk = params["prologue"][pi]
            if kind.startswith("attn"):
                h = run_attn(blk, None, "prologue", pi, mlpk, h, idx)
                continue
            with span("leoam.recurrent"):
                slices = [s.cache["prologue"][pi] for s in states]
                h, new_slices = run_other(blk, kind, mlpk, h, idx, slices)
                for i in range(B):
                    new_caches[i]["prologue"][pi] = new_slices[i]
        for r in range(repeats):
            for pi, (kind, mlpk) in enumerate(period):
                if kind.startswith("attn"):
                    # the programs slice the stacked weights themselves
                    with span("leoam.weights"):
                        w, ri = params["body"][pi], self._repeat_idx[r]
                    h = run_attn(w, ri, "body", pi, mlpk, h, 10 ** 6)
                    continue
                with span("leoam.weights"):
                    blk = jax.tree.map(lambda a: a[r], params["body"][pi])
                with span("leoam.recurrent"):
                    slices = [jax.tree.map(lambda a: a[r],
                                           s.cache["body"][pi])
                              for s in states]
                    h, new_slices = run_other(blk, kind, mlpk, h, 10 ** 6,
                                              slices)
                    for i in range(B):
                        def put(a, b):
                            a = np.asarray(a)
                            a[r] = np.asarray(b)
                            return a
                        new_caches[i]["body"][pi] = jax.tree.map(
                            put, new_caches[i]["body"][pi], new_slices[i])

        with span("leoam.logits"):
            logits = lm._logits(params, cfg, h)[:, 0]
        with span("leoam.sync"):
            if moe_counts is self._moe_zero:
                logits = np.asarray(logits)                  # (B, V)
            else:       # one read: the counts are ready before the logits
                logits, n = jax.device_get((logits, moe_counts))
                prof.update(zip(mlp_mod.MOE_COUNTS, map(int, n)))
        total_s = time.perf_counter() - t_round
        prof["total_s"] = total_s
        if not ecfg.profile:
            prof["attend_s"] = max(0.0, total_s - prof["eval_s"]
                                   - prof["gather_s"] - prof["upload_s"])
        self.round_profiles.append(prof)
        # feed measured per-layer costs back into the θ balance
        n_attn = max(1, len(self.attn_layers))
        tc = prof["attend_s"] / n_attn
        for lid, up_b, disk_b in layer_io:
            self._update_costs(lid, up_b, disk_b, tc)
        out: Dict[int, int] = {}
        for i, sid in enumerate(order):
            s = self.seqs[sid]
            s.cache = new_caches[i]
            s.length += 1
            s.stats.append(round_stats[sid])
            out[sid] = int(np.argmax(logits[i]))
        self.last_logits = dict(zip(order, logits))
        self._round_idx += 1
        if ecfg.sidecar_requant and (ecfg.disk_sidecar or ecfg.pq_abstracts):
            # background repack of append-dirtied sidecars and/or PQ
            # re-encode of append-dirtied codes (chunks quiet for a full
            # round): long-running sequences regain packed disk->host
            # promotions / ADC scoring instead of fp16/min-max forever
            with span("leoam.requant"):
                self.store.requant_sweep(executor=_prefetch_executor())
        return out


class ChunkedAdmission:
    """Resumable chunked prefill of ONE request (vLLM-style).

    Produced by :meth:`BatchedLeoAMEngine.begin_admission`; each
    :meth:`step` forces one fixed-size prefill chunk through the model
    cache (one compiled program for every chunk of every prompt), streams
    the chunk's K/V into the tier store — hot placement synchronous, cold
    replica/abstract writes write-behind exactly as whole-prompt admission
    — and returns control to the caller, so decode rounds interleave with a
    long prompt's admission instead of stalling behind it.  After the final
    prompt chunk the remaining cache rows (zeros) are ingested too, so tier
    coverage, abstracts and the slot-scrub invariant match whole-prompt
    admission chunk for chunk; the resulting sequence is token-identical to
    an ``add_sequence`` admission (tested).  ``result`` resolves to
    (seq id, first token) when ``done``.
    """

    def __init__(self, engine: BatchedLeoAMEngine, sid: int,
                 tokens: np.ndarray, chunk_tokens: int, *,
                 pool_place: bool = True):
        self.engine = engine
        self.sid = sid
        self.tokens = np.asarray(tokens)
        self.S = len(self.tokens)
        self.C = int(chunk_tokens)
        self.pool_place = pool_place
        self.pos = 0
        self.cache = lm.init_decode_cache(engine.cfg, 1, engine.ecfg.max_len)
        self.placement = engine._default_placement()
        self.result: Optional[Tuple[int, int]] = None
        self.cancelled = False
        self.n_steps = 0
        self._t0 = time.perf_counter()
        self._prefill_s = 0.0
        self._ingest_s = 0.0
        self._hit_tokens = 0
        if engine.ecfg.prefix_cache:
            # content-addressable fast path: adopt the matched chunk span
            # by reference, replay its fidelity rows into the cache, and
            # resume prefill at the cold suffix.  The last prompt chunk is
            # ALWAYS recomputed — the first token's logits need a forward
            # pass — and its recomputed KV is dropped by ingest for
            # adopted chunks, never shadowing the shared bytes.
            hit = engine.store.prefix_admit(sid, self.tokens)
            self._hit_tokens = int(hit)
            resume = min((hit // self.C) * self.C,
                         ((self.S - 1) // self.C) * self.C)
            if resume > 0:
                rows = engine.store.prefix_fill_rows(sid, resume)
                self.cache = lm.load_prefix_rows(engine.cfg, self.cache,
                                                 rows, resume)
                self.pos = resume

    @property
    def done(self) -> bool:
        return self.result is not None

    @property
    def remaining(self) -> int:
        """Prompt tokens still to prefill."""
        return max(0, self.S - self.pos)

    def _ingest_rows(self, li: int, layer: int, k: np.ndarray,
                     v: np.ndarray, start: int) -> None:
        eng = self.engine
        eng.store.ingest(li, k, v,
                         eng._layer_placement(layer, self.placement),
                         seq=self.sid, executor=eng._ingest_exec,
                         pool_place=self.pool_place, start=start)

    @decode_thread_only
    def step(self) -> int:
        """Advance one chunk; returns prompt tokens consumed (0 if done).
        Thin decode-thread wrapper over :meth:`_step_impl` — the prefix-
        cache admission worker drives ``_step_impl`` directly (the store
        calls it makes are all lock-protected ``@any_thread``/worker
        paths, and ``pool_place=False`` defers pool mutation)."""
        return self._step_impl()

    def cancel(self) -> None:
        """Abandon a partially-admitted request (deadline expiry or
        client cancellation).  Drains the write-behind futures of the
        chunks already streamed and releases every resource the partial
        admission holds — pool slots, deferred placements, prefix-arena
        refcounts including the unpublished registration plan — via
        :meth:`BatchedLeoAMEngine.abort_admission`; the slot recycles
        immediately.  After cancel, :meth:`step` is a no-op.  Must run on
        the decode thread (like ``step``)."""
        if self.done or self.cancelled:
            return
        self.cancelled = True
        self.engine.abort_admission(self.sid)

    def _step_impl(self) -> int:
        if self.done or self.cancelled:
            return 0
        eng, C = self.engine, self.C
        take = min(C, self.S - self.pos)
        t0 = time.perf_counter()
        chunk_toks = np.zeros(C, np.int64)
        chunk_toks[:take] = self.tokens[self.pos:self.pos + take]
        batch = {"tokens": jnp.asarray(chunk_toks[None], jnp.int32),
                 "start": jnp.int32(self.pos),
                 "length": jnp.int32(self.S)}
        logits, self.cache = eng._prefill_chunk(batch, self.cache)
        t1 = time.perf_counter()
        self._prefill_s += t1 - t0
        for li, layer in enumerate(eng.attn_layers):
            k, v = eng._layer_kv_slice(self.cache, layer, self.pos, C)
            self._ingest_rows(li, layer, k, v, self.pos)
        self._ingest_s += time.perf_counter() - t1
        self.pos += take
        self.n_steps += 1
        if self.pos >= self.S:
            self._finish(logits)
        return take

    def _finish(self, logits) -> None:
        eng = self.engine
        end = -(-self.S // self.C) * self.C      # rows ingested so far
        tail = eng.ecfg.max_len - end
        if tail > 0:
            # zero-fill the uncovered tail chunks: whole-prompt admission
            # ingests the full max_len cache, and parity of tier labels /
            # abstracts / the reused-slot scrub depends on matching it
            t1 = time.perf_counter()
            zk = np.zeros((tail, eng.store.kv_heads, eng.store.head_dim),
                          eng.store.dtype)
            for li, layer in enumerate(eng.attn_layers):
                self._ingest_rows(li, layer, zk, zk, end)
            self._ingest_s += time.perf_counter() - t1
        tok = int(np.argmax(np.asarray(logits)[0]))
        cache_np = jax.tree.map(np.asarray, self.cache)
        eng.seqs[self.sid] = _SeqState(cache=cache_np, length=self.S,
                                       access=AccessTable(eng.n_chunks),
                                       tokens=np.asarray(self.tokens),
                                       prompt_len=self.S)
        if eng.ecfg.prefix_cache:
            # publish the chunks this admission registered ONLY after the
            # write-behind cold writes land: adopters read the arena row's
            # disk replica, so publish-before-fence would expose
            # half-written bytes
            eng.store.ingest_fence(self.sid)
            eng.store.finish_admission(self.sid)
        eng.admit_profiles.append({
            "total_s": time.perf_counter() - self._t0,
            "prefill_s": self._prefill_s, "ingest_s": self._ingest_s,
            "overlapped": float(eng._ingest_exec is not None),
            "chunked": 1.0, "chunks": float(self.n_steps),
            "prefix_hit_tokens": float(self._hit_tokens)})
        self.result = (self.sid, tok)

    def drain(self) -> Tuple[int, int]:
        """Run every remaining chunk back to back (no interleaving)."""
        while not self.done:
            self.step()
        return self.result


class LeoAMEngine:
    """Single-sequence view: a B=1 wrapper over the batched engine,
    preserving the original prefill / decode_step / generate API."""

    def __init__(self, cfg: ArchConfig, params, ecfg: EngineCfg):
        self._engine = BatchedLeoAMEngine(cfg, params, ecfg, max_seqs=1)
        self._sid: Optional[int] = None

    # passthroughs used by benchmarks / scheduler / examples
    @property
    def cfg(self):
        return self._engine.cfg

    @property
    def ecfg(self):
        return self._engine.ecfg

    @property
    def chunk(self):
        return self._engine.chunk

    @property
    def n_chunks(self):
        return self._engine.n_chunks

    @property
    def attn_layers(self):
        return self._engine.attn_layers

    @property
    def store(self):
        return self._engine.store

    @property
    def round_profiles(self):
        return self._engine.round_profiles

    @property
    def admit_profiles(self):
        return self._engine.admit_profiles

    @property
    def length(self) -> int:
        return self._engine.seqs[self._sid].length if self._sid is not None \
            else 0

    @property
    def access(self):
        return self._engine.seqs[self._sid].access

    @property
    def stats(self) -> List[StepStats]:
        if self._sid is None:
            return []
        return self._engine.seqs[self._sid].stats

    # ------------------------------------------------------------------
    def prefill(self, tokens: np.ndarray) -> int:
        if self._sid is not None:        # re-prefill resets, as the old
            self._engine.release(self._sid)  # per-request engine did
        self._sid, tok = self._engine.add_sequence(tokens)
        return tok

    def decode_step(self, token: int) -> int:
        if self._sid is None:
            raise ValueError(
                "decode_step before prefill: call prefill(prompt) (or "
                "generate) to admit the sequence before decoding")
        return self._engine.decode_round({self._sid: token})[self._sid]

    def generate(self, prompt: np.ndarray, n_tokens: int) -> List[int]:
        tok = self.prefill(prompt)
        out = [tok]
        for _ in range(n_tokens - 1):
            tok = self.decode_step(tok)
            out.append(tok)
        return out
