"""The decode round's own spans and counters (``serving/tracing.py``):
every phase reported as self time in ``round_profiles``, the phases
inside the round body's measured time, the spans nested in a profiler
trace, tokens unchanged by tracing, and compiles credited to a phase."""

import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serving import tracing
from repro.serving.engine import BatchedLeoAMEngine, EngineCfg

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import trace_reduce  # noqa: E402

PROMPTS = (47, 64, 57)
INSIDE = [p for p in tracing.DECODE_SPANS if p not in tracing.OUTSIDE_TOTAL]
# phases a GQA round with no recurrent layer and no requant sweep enters
ENTERED = set(tracing.DECODE_SPANS) - {"leoam.recurrent", "leoam.requant"}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("phi4-mini-3.8b", smoke=True)
    cfg = dataclasses.replace(
        cfg, leoam=dataclasses.replace(cfg.leoam, chunk_size=16,
                                       importance_rate=0.4, early_rate=0.6,
                                       min_seq_for_sparse=32))
    params = lm.init(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _engine(cfg, params, rng, n=len(PROMPTS)):
    eng = BatchedLeoAMEngine(cfg, params, EngineCfg(max_len=128),
                             max_seqs=n, device_chunk_budget=16)
    toks = {}
    for L in PROMPTS[:n]:
        sid, tok = eng.add_sequence(rng.randint(2, cfg.vocab_size, L))
        toks[sid] = tok
    return eng, toks


def _decode(eng, toks, rounds):
    out = []
    for _ in range(rounds):
        toks = eng.decode_round(toks)
        out.append(dict(toks))
    return out, toks


def test_every_phase_is_in_the_round_profile(setup, rng):
    cfg, params = setup
    eng, toks = _engine(cfg, params, rng)
    _decode(eng, toks, 3)
    for prof in eng.round_profiles:
        for p in tracing.DECODE_SPANS:
            assert prof[f"{p}_s"] >= 0.0, p
        # the keys the θ balance and the benchmark read keep their meaning
        assert {"eval_s", "gather_s", "upload_s", "attend_s",
                "total_s"} <= set(prof)
        assert prof["attend_s"] == pytest.approx(
            max(0.0, prof["total_s"] - prof["eval_s"] - prof["gather_s"]
                - prof["upload_s"]))
        assert set(prof["compiles_by_phase"]) <= set(tracing.DECODE_SPANS)
        assert sum(prof["compiles_by_phase"].values()) == prof["compiles"]
    last = eng.round_profiles[-1]
    for p in ENTERED:
        assert last[f"{p}_s"] > 0.0, p
    eng.store.close()


def test_phases_lie_inside_the_measured_round(setup, rng, monkeypatch):
    cfg, params = setup
    eng, toks = _engine(cfg, params, rng)
    select = eng._select_chunks_batched
    in_select = []

    def spanned(*a, **k):
        """Self time the round gains inside the selection: its
        ``leoam.select.*`` spans and the syncs nested in them."""
        rnd = tracing._local.round
        before = sum(rnd.self_s.values())
        out = select(*a, **k)
        in_select[-1] += sum(rnd.self_s.values()) - before
        return out

    monkeypatch.setattr(eng, "_select_chunks_batched", spanned)
    for _ in range(3):
        in_select.append(0.0)
        toks = eng.decode_round(toks)
    for prof, sel in zip(eng.round_profiles, in_select):
        inside = sum(prof[f"{p}_s"] for p in INSIDE)
        assert inside <= prof["total_s"]
        assert inside >= 0.5 * prof["total_s"]
        assert sel > 0.0
        assert sum(prof[f"{p}_s"] for p in INSIDE
                   if p.startswith("leoam.select.")) <= sel
        assert sel <= prof["eval_s"]
    eng.store.close()


def test_spans_nest_inside_one_round_in_a_trace(setup, rng, tmp_path):
    cfg, params = setup
    eng, toks = _engine(cfg, params, rng)
    toks = eng.decode_round(toks)           # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            _decode(eng, toks, 2)
    finally:
        jax.profiler.stop_trace()
    eng.store.close()
    t = trace_reduce.load(trace_reduce.xplane_file(str(tmp_path)),
                          tracing.DECODE_SPANS, cpu=True)
    rounds = sorted((s, e) for n, s, e in t.spans if n == "leoam.round")
    assert len(rounds) == 2
    seen = set()
    for n, s, e in t.spans:
        if n == "leoam.round":
            continue
        seen.add(n)
        assert sum(1 for r0, r1 in rounds if r0 <= s and e <= r1) == 1, n
    assert seen | {"leoam.round"} == ENTERED


def test_tracing_leaves_the_served_tokens_unchanged(setup, tmp_path):
    cfg, params = setup
    streams = []
    for traced in (False, True):
        eng, toks = _engine(cfg, params, np.random.RandomState(3))
        if traced:
            jax.profiler.start_trace(str(tmp_path))
        try:
            out, _ = _decode(eng, toks, 3)
        finally:
            if traced:
                jax.profiler.stop_trace()
        eng.store.close()
        streams.append(out)
    assert streams[0] == streams[1]


def test_a_new_bounds_shape_compiles_in_a_named_phase(setup, rng):
    """The sequences' chunk count grows from 3 to 4 in the third round:
    the bounds take it as a shape, so that round compiles, and the
    compile is credited to the phase that dispatched it."""
    cfg, params = setup
    eng, toks = _engine(cfg, params, rng, n=1)         # one 47-token prompt
    jax.clear_caches()
    _decode(eng, toks, 3)
    first, _, grown = eng.round_profiles
    assert first["compiles"] >= 1
    assert grown["compiles"] >= 1
    assert grown["compile_s"] > 0.0
    assert grown["compiles_by_phase"].get("leoam.select.bounds", 0) >= 1
    assert set(grown["compiles_by_phase"]) <= set(tracing.DECODE_SPANS)
    eng.store.close()


def test_spans_outside_a_round_only_annotate():
    with tracing.span("leoam.sync") as s:
        pass
    assert s.name == "leoam.sync"
    with tracing.Round() as rnd:
        with tracing.span("leoam.mlp"):
            with tracing.span("leoam.sync"):
                pass
    assert rnd.self_s["leoam.mlp"] >= 0.0
    assert rnd.self_s["leoam.sync"] > 0.0
    prof = rnd.profile()
    assert prof["compiles"] == 0 and prof["compiles_by_phase"] == {}
    assert tracing._local.round is None and tracing._local.stack == []


# ---------------------------------------------------------------------------
# Expert layers' dispatch counts
# ---------------------------------------------------------------------------

def _dsv2(**over):
    """DeepSeek-V2-Lite's small variant holding experts 4..7 of a router
    over 16, top-4, the routing weights not renormalised."""
    cfg = get_config("deepseek-v2-lite-16b", smoke=True)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_experts=4, top_k=4,
                                     router_width=16, first_expert=4,
                                     norm_topk=False),
        leoam=dataclasses.replace(cfg.leoam, chunk_size=16,
                                  importance_rate=0.4, early_rate=0.6,
                                  min_seq_for_sparse=32), **over)
    return cfg, lm.init(cfg, jax.random.PRNGKey(2))


def _plain_counts(cfg, blk, h, y):
    """The counts of one expert layer from a plain top-k of its router
    logits."""
    from repro.models.common import rms_norm
    m = cfg.moe
    x = np.asarray(rms_norm(h + y, blk["ln2"], cfg.norm_eps), np.float64)
    logits = x[:, 0] @ np.asarray(blk["mlp"]["router"], np.float64)
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :m.top_k]
    local = top - m.first_expert
    held = (local >= 0) & (local < m.n_experts)
    return {"moe_picks_held": int(held.sum()),
            "moe_rows": m.n_experts * x.shape[0],
            "moe_experts_hit": len(set(local[held].tolist()))}


def test_an_expert_round_counts_its_dispatch(rng, monkeypatch):
    from repro.models.mlp import MOE_COUNTS
    cfg, params = _dsv2()
    eng, toks = _engine(cfg, params, rng)
    program = eng._layer_program
    want = []

    def spy(which, where, pi, mlpk):
        fn = program(which, where, pi, mlpk)
        if which != "post" or mlpk != "moe":
            return fn

        def post(w, r, h, y, counts=None):
            blk = w if r is None else jax.tree.map(lambda a: a[r], w)
            want.append(_plain_counts(cfg, blk, h, y))
            return fn(w, r, h, y, counts)
        return post

    monkeypatch.setattr(eng, "_layer_program", spy)
    for _ in range(2):
        want.clear()
        toks = eng.decode_round(toks)
        n_moe = sum(m == "moe" for m in cfg.mlp_kinds())
        assert len(want) == n_moe >= 2
        prof = eng.round_profiles[-1]
        for k in MOE_COUNTS:
            assert prof[k] == sum(c[k] for c in want), k
        assert prof["moe_rows"] == n_moe * 4 * len(PROMPTS)
        assert prof["moe_picks_held"] > 0
    eng.store.close()


def test_a_dense_round_has_no_expert_counts(setup, rng):
    from repro.models.mlp import MOE_COUNTS
    cfg, params = setup
    eng, toks = _engine(cfg, params, rng)
    _decode(eng, toks, 2)
    for prof in eng.round_profiles:
        assert not set(MOE_COUNTS) & set(prof)
    eng.store.close()


def _reads_per_round(cfg, params, monkeypatch):
    """Device->host reads the engine makes in each of three rounds: one
    per ``jax.device_get`` call, whatever it holds, and one per
    ``np.asarray`` of a device array."""
    import repro.serving.engine as engine_mod
    reads = [0]

    class CountedNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kw):
            reads[0] += isinstance(a, jax.Array)
            return np.asarray(a, *args, **kw)

    def counted_get(x, _get=jax.device_get):
        reads[0] += 1
        return _get(x)

    eng, toks = _engine(cfg, params, np.random.RandomState(4))
    monkeypatch.setattr(engine_mod, "np", CountedNumpy())
    monkeypatch.setattr(jax, "device_get", counted_get)
    out = []
    for _ in range(3):
        reads[0] = 0
        toks = eng.decode_round(toks)
        out.append(reads[0])
    monkeypatch.undo()
    eng.store.close()
    return out


def test_expert_counts_add_no_device_read(monkeypatch):
    """A round of the expert stack reads the device as often as the same
    stack with dense MLPs: the counts come back with the logits."""
    experts = _dsv2()
    dense = _dsv2(mlp_pattern=("dense",))
    assert "moe" in experts[0].mlp_kinds()
    assert "moe" not in dense[0].mlp_kinds()
    got = _reads_per_round(*experts, monkeypatch)
    assert got == _reads_per_round(*dense, monkeypatch)
    assert min(got) > 0
