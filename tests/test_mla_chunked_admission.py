"""MLA chunked admission (PR 5): DeepSeek-class absorbed-MLA models ride
the full bucketed/chunked admission pipeline — latent single-plane tier
store, chunk-by-chunk prefill under running decode rounds, write-behind
partial ingest — token-identical to whole-prompt ``add_sequence``
(property-tested under randomized interleavings and at bucket edges), plus
the adaptive per-round prefill budget derived from measured EWMAs."""

import dataclasses
import math

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import compression
from repro.serving.offload import DEVICE, DISK, HOST, TieredKVStore
from repro.serving.scheduler import ContinuousBatcher, Request, SchedulerCfg

_SETUP = {}


def _setup():
    """Module-lazy MLA smoke model (the hypothesis shim can't take
    fixtures).  deepseek-v2-lite smoke: MLA kv_lora 32 + rope 8 (latent
    width 40), MoE body layers — the admission path's hardest case."""
    if not _SETUP:
        from repro.configs import get_config
        from repro.models import lm
        cfg = get_config("deepseek-v2-lite-16b", smoke=True)
        cfg = dataclasses.replace(
            cfg, leoam=dataclasses.replace(cfg.leoam, chunk_size=16,
                                           importance_rate=0.4,
                                           early_rate=0.6,
                                           min_seq_for_sparse=32))
        _SETUP["cfg"] = cfg
        _SETUP["params"] = lm.init(cfg, jax.random.PRNGKey(1))
    return _SETUP["cfg"], _SETUP["params"]


def _ecfg(**kw):
    from repro.serving.engine import EngineCfg
    return EngineCfg(max_len=128, selection="tree", **kw)


def _engine(max_seqs=1, **kw):
    from repro.serving.engine import BatchedLeoAMEngine
    cfg, params = _setup()
    return BatchedLeoAMEngine(cfg, params, _ecfg(**kw), max_seqs=max_seqs)


def _gen(eng, prompt, n_new=3):
    sid, tok = eng.add_sequence(prompt)
    out = [tok]
    toks = {sid: tok}
    for _ in range(n_new):
        toks = eng.decode_round(toks)
        out.append(toks[sid])
    eng.release(sid)
    return out


# ---------------------------------------------------------------------------
# Latent store layout
# ---------------------------------------------------------------------------


def test_latent_store_single_plane_accounting(rng):
    """The absorbed-MLA store keeps ONE latent plane: chunk/row bytes cover
    exactly the latent payload (no phantom V), abstracts are the min/max
    box over the latent rows, and the packed sidecar bytes obey the
    single-plane codec identity."""
    D = 40
    st_ = TieredKVStore(1, 4, 16, 1, D, n_seqs=1, transit_codec="int4",
                        latent=True, use_pool=True, disk_sidecar=True)
    assert st_.planes == 1
    assert st_.chunk_bytes == 16 * D * 2          # one fp16 latent plane
    assert st_.row_bytes == D * 2
    assert st_.abstract_bytes == 2 * D * 2        # min + max, not K + V
    lat = rng.randn(48, 1, D).astype(np.float16)
    st_.ingest(0, lat, None, {0: DEVICE, 1: HOST, 2: DISK})
    km, kn = st_.read_abstracts(0, [2])
    np.testing.assert_allclose(km[0], lat[32:48].max(0), atol=1e-3)
    np.testing.assert_allclose(kn[0], lat[32:48].min(0), atol=1e-3)
    assert st_.pools[0].kv.shape == (st_.pools[0].n_slots + 1, 1, 16, 1, D)
    # packed sidecar identity for the single plane
    st_.demote(0, [2], to=DISK)
    _, _, fst = st_.fetch_chunks_pooled(0, {0: [2]})
    packed = st_.chunk_bytes * compression.codec_ratio("int4", group=16)
    assert fst.disk_bytes == pytest.approx(packed)
    st_.close()


def test_latent_partial_ingest_matches_whole(rng):
    """Chunk-aligned partial ingest of LATENT rows (start=...) lands the
    same replicas, abstracts, tiers and billed bytes as one whole-sequence
    ingest — byte-for-byte in the disk replica."""
    D = 40
    lat = rng.randn(64, 1, D).astype(np.float16)
    place = {0: DEVICE, 1: HOST, 2: DISK, 3: DISK}
    whole = TieredKVStore(1, 4, 16, 1, D, n_seqs=1, transit_codec="int4",
                          latent=True)
    whole.ingest(0, lat, None, place)
    part = TieredKVStore(1, 4, 16, 1, D, n_seqs=1, transit_codec="int4",
                         latent=True)
    for start in (0, 16, 32):
        n = 16 if start < 32 else 32
        part.ingest(0, lat[start:start + n], None, place, start=start)
    np.testing.assert_array_equal(np.asarray(whole._disk),
                                  np.asarray(part._disk))
    np.testing.assert_array_equal(whole._abs_km, part._abs_km)
    np.testing.assert_array_equal(whole._abs_kn, part._abs_kn)
    assert list(whole.tier[0, 0]) == list(part.tier[0, 0])
    assert whole.log.total() == part.log.total()
    whole.close()
    part.close()


def test_latent_sidecar_partial_ingest_matches_whole(rng):
    """Partial vs whole ingest parity extends to the packed int4 sidecar
    (payload + scales) and its billing."""
    D = 40
    lat = rng.randn(64, 1, D).astype(np.float16)
    stores = []
    for starts in ((0,), (0, 32)):
        s = TieredKVStore(1, 4, 16, 1, D, n_seqs=1, transit_codec="int4",
                          latent=True, disk_sidecar=True)
        for start in starts:
            n = 64 if len(starts) == 1 else 32
            s.ingest(0, lat[start:start + n], None,
                     {c: DISK for c in range(4)}, start=start)
        stores.append(s)
    whole, part = stores
    np.testing.assert_array_equal(np.asarray(whole._disk_q),
                                  np.asarray(part._disk_q))
    np.testing.assert_array_equal(np.asarray(whole._disk_scale),
                                  np.asarray(part._disk_scale))
    assert whole.log.total() == part.log.total()
    whole.close()
    part.close()


# ---------------------------------------------------------------------------
# Engine: MLA end-to-end + bucket edges
# ---------------------------------------------------------------------------


_ENGINES = {}


def _bucket_pair():
    if not _ENGINES:
        _ENGINES["exact"] = _engine(bucket_prefill=False)
        _ENGINES["bucket"] = _engine(bucket_prefill=True)
    return _ENGINES["exact"], _ENGINES["bucket"]


@pytest.mark.parametrize("L", [31, 32, 33, 63, 64, 65])
def test_mla_bucketed_prefill_token_identical_at_bucket_edges(L):
    """Property (bucket edges L, L±1): the MLA cache-zeroing path honors
    the traced true length — bucketed MLA admission decodes the exact
    token stream of exact-length admission."""
    cfg, _ = _setup()
    prompt = np.random.RandomState(100 + L).randint(2, cfg.vocab_size, L)
    exact, bucket = _bucket_pair()
    assert _gen(bucket, prompt) == _gen(exact, prompt)


def test_mla_mixed_lengths_compile_log_programs():
    """O(log L) compiled prefill programs hold for MLA traffic too: >= 12
    distinct prompt lengths stay within ceil(log2(max_len)) + 2 programs,
    first tokens matching the exact-length path."""
    cfg, _ = _setup()
    exact, bucket = _bucket_pair()
    rng = np.random.RandomState(11)
    lengths = list(range(17, 113, 8))
    assert len(set(lengths)) >= 12
    for L in lengths:
        p = rng.randint(2, cfg.vocab_size, L)
        sid_b, tok_b = bucket.add_sequence(p)
        bucket.release(sid_b)
        sid_e, tok_e = exact.add_sequence(p)
        exact.release(sid_e)
        assert tok_b == tok_e, L
    limit = math.ceil(math.log2(bucket.ecfg.max_len)) + 2
    assert bucket.prefill_programs <= limit, (bucket.prefill_programs, limit)
    assert exact.prefill_programs >= len(lengths)


@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_mla_chunked_admission_interleaved_matches_serial(seed):
    """Property: MLA chunked admission stepped at RANDOM interleavings
    with a running sequence's decode rounds produces token streams
    identical to whole-prompt admission at the same round schedule."""
    cfg, _ = _setup()
    rng = np.random.RandomState(seed)
    pa = rng.randint(2, cfg.vocab_size, 41)
    pb = rng.randint(2, cfg.vocab_size, 57)
    pre_rounds = int(rng.randint(0, 3))
    interleave = [bool(b) for b in rng.randint(2, size=8)]

    def run(chunked: bool):
        eng = _engine(max_seqs=2, prefill_chunk_tokens=32)
        sa_, ta = eng.add_sequence(pa)
        outs = {sa_: [ta]}
        toks = {sa_: ta}
        for _ in range(pre_rounds):
            toks = eng.decode_round(toks)
            outs[sa_].append(toks[sa_])
        if chunked:
            adm = eng.begin_admission(pb)
            for do_round in interleave:
                adm.step()
                if adm.done:
                    break
                if do_round:
                    toks = eng.decode_round(toks)
                    outs[sa_].append(toks[sa_])
            sb, tb = adm.drain()
        else:
            sb, tb = eng.add_sequence(pb)
        outs[sb] = [tb]
        toks[sb] = tb
        for _ in range(3):
            toks = eng.decode_round(toks)
            for s, t in toks.items():
                outs[s].append(t)
        eng.store.close()
        return outs[sa_], outs[sb]

    a_chunk, b_chunk = run(True)
    a_ser, b_ser = run(False)
    n = min(len(a_chunk), len(a_ser))
    assert a_chunk[:n] == a_ser[:n]
    assert b_chunk == b_ser


@settings(max_examples=2, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_mla_scheduler_chunked_admission_parity(seed):
    """Acceptance: an MLA model runs ContinuousBatcher(chunked_admission=
    True) end-to-end with token streams identical to whole-prompt
    admission, for random arrival orders and budgets."""
    cfg, params = _setup()
    from repro.serving.engine import BatchedLeoAMEngine
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(2, cfg.vocab_size, n) for n in (48, 57, 64, 50)]
    order = list(rng.permutation(4))
    budget = int(rng.choice([16, 32, 64]))

    def drive(chunked: bool):
        eng = BatchedLeoAMEngine(cfg, params,
                                 _ecfg(prefill_chunk_tokens=16),
                                 max_seqs=3)
        b = ContinuousBatcher(
            cfg=SchedulerCfg(max_active=2, chunk=16,
                             chunked_admission=chunked,
                             prefill_round_tokens=budget),
            engine=eng)
        for i in order:
            b.submit(Request(i, prompts[i], max_new=4))
        out = {r.rid: r.out for r in b.run()}
        eng.store.close()
        return out

    assert drive(True) == drive(False), (order, budget)


def test_mla_partial_engine_ingest_matches_whole_ingest(rng):
    """Chunked MLA admission lands the same replicas AND abstracts in the
    tier store as whole-prompt admission of the same prompt, with
    identical tier labels.

    Values agree to 2**-10 relative (one fp16 ulp at the bottom of a
    binade) or 2**-20 absolute, not bitwise: XLA:CPU picks its matmul
    kernel by row count, so the latent projection ``x @ wkv_a`` over a
    16-row chunk differs in the last f32 bit from the same rows inside the
    64-row bucket (reproducible with a bare jitted ``x @ w``).  Those
    last-bit differences carry through the layers at the f32 roundoff
    level, and a few latents round to neighbouring fp16 values; a latent
    that cancels to near zero keeps the absolute roundoff of its larger
    summands (about 5e-7), hence the absolute term."""
    cfg, params = _setup()
    from repro.serving.engine import BatchedLeoAMEngine
    prompt = rng.randint(2, cfg.vocab_size, 57)
    whole = BatchedLeoAMEngine(cfg, params, _ecfg(), max_seqs=1)
    whole.add_sequence(prompt)
    whole.store.ingest_fence(0)
    chunked = BatchedLeoAMEngine(cfg, params,
                                 _ecfg(prefill_chunk_tokens=16), max_seqs=1)
    chunked.begin_admission(prompt).drain()
    chunked.store.ingest_fence(0)
    for a, b in ((whole.store._disk, chunked.store._disk),
                 (whole.store._abs_km, chunked.store._abs_km),
                 (whole.store._abs_kn, chunked.store._abs_kn)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2.0 ** -10, atol=2.0 ** -20)
    assert (list(whole.store.tier[0].reshape(-1))
            == list(chunked.store.tier[0].reshape(-1)))
    whole.store.close()
    chunked.store.close()


def test_mla_oversized_prompt_and_capacity_raise():
    """Admission-path guards raise actionable ValueErrors (not asserts):
    oversized prompts before the slot pop, capacity exhaustion, and
    unaligned chunk sizes."""
    cfg, params = _setup()
    eng = _engine(max_seqs=1)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_sequence(np.arange(4000) % cfg.vocab_size)
    assert eng.free_slots == 1            # no slot leaked
    with pytest.raises(ValueError, match="multiple of the store chunk"):
        eng.begin_admission(np.arange(32), chunk_tokens=24)
    sid, _ = eng.add_sequence(np.arange(2, 50))
    with pytest.raises(ValueError, match="capacity"):
        eng.add_sequence(np.arange(2, 50))
    eng.release(sid)
    eng.store.close()


# ---------------------------------------------------------------------------
# MoE no-drop inference dispatch (what makes chunked == whole possible)
# ---------------------------------------------------------------------------


def test_moe_no_drop_rows_independent_of_batch_shape(rng):
    """Inference MoE dispatch (no_drop): a token's output is independent
    of the surrounding batch shape — the same rows fed at T=8 and T=32
    produce bitwise-identical outputs, while the training dispatch may
    capacity-drop differently."""
    import jax.numpy as jnp
    from repro.models import lm as lm_mod
    cfg, params = _setup()
    blk = params["body"][0]
    moe_blk = {k: jax.tree.map(lambda a: a[0], v) for k, v in blk.items()}
    x = jnp.asarray(rng.randn(1, 32, cfg.d_model).astype(np.float32))
    y_whole, _ = lm_mod._apply_mlp(moe_blk, cfg, "moe", x, None,
                                   no_drop=True)
    y_chunk0, _ = lm_mod._apply_mlp(moe_blk, cfg, "moe", x[:, :8], None,
                                    no_drop=True)
    np.testing.assert_array_equal(np.asarray(y_whole[:, :8]),
                                  np.asarray(y_chunk0))


# ---------------------------------------------------------------------------
# Adaptive prefill budget
# ---------------------------------------------------------------------------


def test_adaptive_prefill_budget_derivation():
    """The derived budget honors the target stall bound: with measured
    idle-round and chunk-step EWMAs, budget = k * chunk_tokens where k is
    the largest count with idle + k*chunk <= idle*(1+frac); clamped to one
    chunk so admission always progresses."""
    b = ContinuousBatcher(make_engine=lambda: None,
                          cfg=SchedulerCfg(adaptive_prefill_budget=True,
                                           target_stall_frac=0.5,
                                           prefill_round_tokens=64))
    # no measurements yet: static fallback
    assert b._prefill_budget() == 64
    b._idle_ewma, b._round_ewma = 0.2, 0.25
    b._chunk_ewma, b._chunk_tokens = 0.02, 16
    assert b._prefill_budget() == 5 * 16          # 0.5*0.2/0.02 = 5 chunks
    assert b.stats()["prefill_round_tokens"] == 80.0
    # chunk steps dearer than the whole tolerated stall: still one chunk
    b._chunk_ewma = 0.5
    assert b._prefill_budget() == 16
    # bound check: derived k satisfies the analytic model's gap bound
    from repro.core.pipeline import chunked_admission_model
    m = chunked_admission_model(0.02, 5, 0.2, 5)
    assert m["max_round_gap_chunked_s"] <= 0.2 * 1.5 + 1e-9


def test_adaptive_prefill_budget_end_to_end():
    """Live run: adaptive chunked admission completes, matches the static
    token streams, and stats() exports the derived budget + chunk EWMA."""
    cfg, params = _setup()
    from repro.serving.engine import BatchedLeoAMEngine
    rng = np.random.RandomState(3)
    prompts = [rng.randint(2, cfg.vocab_size, n) for n in (48, 57, 40)]

    def drive(adaptive: bool):
        eng = BatchedLeoAMEngine(cfg, params,
                                 _ecfg(prefill_chunk_tokens=16), max_seqs=3)
        b = ContinuousBatcher(
            cfg=SchedulerCfg(max_active=2, chunk=16, chunked_admission=True,
                             prefill_round_tokens=16,
                             adaptive_prefill_budget=adaptive),
            engine=eng)
        for i, p in enumerate(prompts):
            b.submit(Request(i, p, max_new=4))
        out = {r.rid: r.out for r in b.run()}
        stt = b.stats()
        eng.store.close()
        return out, stt

    out_a, stt = drive(True)
    out_s, _ = drive(False)
    assert out_a == out_s            # budget moves latency, never values
    assert "prefill_round_tokens" in stt
    assert "chunk_step_ewma_s" in stt
    assert stt["prefill_round_tokens"] >= 16
