"""The harness on latent attention (MLA) with routed and shared experts:
DeepSeek-V2-Lite's small variant, from a test-only configuration built by
``cell.conf_of_arch`` (no file of its own and no cell in BENCHMARK.json),
driven end to end on the CPU against the reference; the reference's
expert share; and the operation counts, pinned for the GQA file and
counted from the program's layout for MLA and experts."""
import copy

import jax
import numpy as np
import pytest

import cell
import flops
import reference
import run
import trace_reduce
from test_bench_run import SMOKE_LIMIT, _run

DSV2 = "deepseek-v2-lite-16b"
CELL = "dsv2-smoke"
# The program keeps its cache rows in float16; the reference computes in
# float32.  Where two chunks' bounds or two experts' router weights lie
# within that rounding, the two choose differently and the widest gap
# reads 0.1-1.7: in 24 rounds seeds 3, 7 and 9 of 20 tried do, and all
# of them read 0.0 with the store held in float32 (a diagnostic patch).
# These seeds meet no such tie.
SEEDS = {"served budget": 2**31 + 1, "full selection": 2**31 + 2,
         "control": 2**31 + 3, "altered": 2**31 + 4}


@pytest.fixture(autouse=True)
def _harness_for_tests(monkeypatch):
    monkeypatch.setattr(run, "configure_jax", lambda: None)


def _conf():
    from repro.configs import get_config
    conf = cell.conf_of_arch(get_config(DSV2),
                             cell.config("phi4-mini-3.8b")["leoam"])
    conf.update(program={"arch": DSV2, "overrides": {}}, engine={},
                scheduler={})
    return conf


@pytest.fixture
def dsv2(monkeypatch):
    """Point the harness at the test-only configuration, with the LeoAM
    rules given."""
    def use(**rules):
        conf = _conf()
        conf["leoam"].update(rules)
        monkeypatch.setattr(cell, "workload", lambda name: {
            "name": name, "config": DSV2, "traffic": "decode-8k",
            "chips": 1})
        monkeypatch.setattr(cell, "config",
                            lambda name: copy.deepcopy(conf))
    return use


@pytest.mark.parametrize("budget", ["served budget", "full selection"])
def test_the_harness_serves_mla_and_experts_correctly(dsv2, budget):
    if budget == "full selection":
        dsv2(importance_rate=1.0, early_rate=1.0)
    else:
        dsv2()
    res = _run(CELL, SEEDS[budget])
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= SMOKE_LIMIT
    assert res["checks"]["tokens_compared"]["value"] > 0


def test_the_fp8_control_fails_on_mla_and_experts(dsv2):
    dsv2()
    res = _run(CELL, SEEDS["control"], control="fp8")
    assert res["correct"] is False
    assert res["readings"]["program"]["logit_gap"] <= SMOKE_LIMIT
    assert res["checks"]["logit_gap"]["value"] > SMOKE_LIMIT


def test_an_altered_token_fails_on_mla_and_experts(dsv2, monkeypatch):
    from repro.serving.engine import BatchedLeoAMEngine
    orig = BatchedLeoAMEngine.decode_round
    calls = {"n": 0}

    def altered(self, tokens):
        out = orig(self, tokens)
        calls["n"] += 1
        if calls["n"] == 3:                 # one token, where it is made
            sid = min(out)
            out[sid] = (out[sid] + 1) % self.cfg.vocab_size
        return out

    dsv2()
    monkeypatch.setattr(BatchedLeoAMEngine, "decode_round", altered)
    res = _run(CELL, SEEDS["altered"])
    assert calls["n"] >= 3
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > SMOKE_LIMIT


def test_a_traced_run_counts_the_latent_attend(dsv2, monkeypatch):
    """The probe records the latent attend's calls and the readers count
    them by the MLA formulas.  The CPU trace has no device plane, so the
    attend programs' device time is given here."""
    dsv2()
    monkeypatch.setattr(trace_reduce, "program_s", lambda tr, which: 1.0)
    res = _run(CELL, SEEDS["served budget"], trace=True)
    m = res["metrics"]
    assert 0.0 < m["attend_roofline"]["value"] < 100.0
    assert m["decode_mfu"]["value"] > 0.0
    assert res["correct"] is True


@pytest.mark.parametrize("norm, scale", [(False, 1.0), (True, 2.5)])
def test_the_expert_share_sums_to_the_whole_layer(norm, scale):
    """Held ranges that are disjoint and cover every expert, each with
    the router at its full width (its held experts first) and the shared
    experts counted in one of them, add up to the uncut layer."""
    rng = np.random.default_rng(0)
    S, d, ff, E = 5, 16, 8, 8

    def w(*shape):
        return rng.normal(0.0, shape[-2] ** -0.5, shape).astype(np.float32)

    m = {"router": w(d, E), "w_gate": w(E, d, ff), "w_up": w(E, d, ff),
         "w_down": w(E, ff, d), "shared_w_gate": w(d, 2 * ff),
         "shared_w_up": w(d, 2 * ff), "shared_w_down": w(2 * ff, d)}
    h = rng.normal(size=(S, d)).astype(np.float32)
    conf = {"n_routed_experts": E, "num_experts_per_tok": 3,
            "norm_topk_prob": norm, "routed_scaling_factor": scale,
            "n_shared_experts": 2}
    whole = np.asarray(reference._moe(m, h, conf, "f32"))
    parts = np.zeros_like(whole)
    for i, (lo, hi) in enumerate([(0, 3), (3, 4), (4, 8)]):
        held = np.r_[lo:hi, 0:lo, hi:E]
        share = dict(m, router=m["router"][:, held],
                     **{k: m[k][lo:hi] for k in ("w_gate", "w_up", "w_down")})
        parts += np.asarray(reference._moe(share, h, dict(
            conf, n_routed_experts=hi - lo, n_routed_experts_published=E,
            n_shared_experts=2 if i == 0 else 0), "f32"))
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("B, nmax, want", [
    (4, 74, {"flops": 308330496.0, "bytes": 96468992.0}),
    (1, 1, {"flops": 19673088.0, "bytes": 19136512.0}),
    (3, 20, {"flops": 103845888.0, "bytes": 34603008.0}),
])
def test_the_gqa_counts_are_unchanged(B, nmax, want):
    conf = cell.config("phi4-mini-3.8b")
    assert flops.params_per_token(conf) == 3835822080
    assert flops.attn_flops_per_key(conf) == 12288
    assert flops.attend_cost(conf, B, nmax, 64) == want


def _multiplied(tree, top_k):
    """Weights of a (possibly stacked) layout tree that one decode token
    multiplies: every matrix but the norm scales, and of the routed
    experts ``top_k`` of each layer's."""
    n = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(p, "key", p)) for p in path]
        if keys[-1] in ("ln1", "ln2", "kv_norm"):
            continue
        if keys[-1] in ("w_gate", "w_up", "w_down") and leaf.ndim >= 3:
            n += top_k * leaf.size // leaf.shape[-3]
        else:
            n += leaf.size
    return n


def test_the_mla_and_expert_counts_follow_the_program_layout():
    from repro.models import lm
    cfg = cell.arch(_conf(), smoke=True)
    conf = cell.conf_of_arch(cfg, {})
    layout = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    k = cfg.moe.top_k
    want = (sum(_multiplied(b, k) for b in layout["prologue"])
            + sum(_multiplied(b, k) for b in layout["body"])
            + layout["lm_head"].size)
    assert [flops.moe_layer(conf, i) for i in range(cfg.n_layers)] == \
        [m == "moe" for m in cfg.mlp_kinds()]
    assert flops.params_per_token(conf) == want
    r, rope = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
    assert flops.attn_flops_per_key(conf) == 2 * cfg.n_heads * (2 * r + rope)
    # the latent rows gathered, and wv_b and wo, each once a call
    c = flops.attend_cost(conf, 2, 3, 8)
    v = cfg.mla.v_head_dim
    assert c["bytes"] == 2 * (2 * 3 * 8 * (r + rope) + cfg.n_heads * r * v
                              + cfg.n_heads * v * cfg.d_model)


def test_overrides_reach_inside_the_expert_block():
    conf = dict(_conf(), program={"arch": DSV2, "overrides": {
        "n_layers": 5, "moe": {"n_experts": 8}}})
    cfg = cell.arch(conf)
    assert cfg.n_layers == 5
    assert cfg.moe.n_experts == 8
    assert (cfg.moe.top_k, cfg.moe.d_ff_expert, cfg.moe.n_shared) == \
        (6, 1408, 2)


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", DSV2])
def test_the_reference_boxes_are_the_store_abstracts(arch):
    """The reference's chunk boxes (``_boxes`` over the zero-initialised
    cache, then ``Chooser.append``) equal the program's stored abstracts,
    chunks past the prompt and the keys or latent rows decode appends to
    them included.  The store rounds rows to float16; its abstracts of
    appended rows are taken before that rounding."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.serve import shutdown
    from repro.models import lm
    from repro.serving.engine import BatchedLeoAMEngine, EngineCfg
    cfg = get_config(arch, smoke=True)
    chunk, max_len, P = cfg.leoam.chunk_size, 256, 170
    eng = BatchedLeoAMEngine(cfg, lm.init(cfg, jax.random.key(0)),
                             EngineCfg(max_len=max_len), max_seqs=1)
    try:
        sid, tok = eng.add_sequence(np.arange(2, 2 + P))
        eng.store.ingest_fence(sid)
        n_layers = eng.store._abs_km.shape[1]
        rows = [np.asarray(eng.store._disk[sid, i, :, 0], np.float64)
                .reshape(max_len, *eng.store._disk.shape[-2:])
                for i in range(n_layers)]
        boxes = [reference._boxes(jnp.asarray(r), chunk=chunk)
                 for r in rows]
        chooser = reference.Chooser(
            {"chunk_size": chunk}, max_len // chunk,
            [np.asarray(hi, np.float64) for hi, _ in boxes],
            [np.asarray(lo, np.float64) for _, lo in boxes])
        for p in range(P, P + chunk + 2):           # into a fresh chunk
            tok = eng.decode_round({sid: tok})[sid]
            for i in range(n_layers):
                chooser.append(i, p, np.asarray(
                    eng.store._disk[sid, i, p // chunk, 0, p % chunk],
                    np.float64))
        for i in range(n_layers):
            np.testing.assert_allclose(chooser.kmax[i],
                                       eng.store._abs_km[sid, i],
                                       rtol=2**-10, atol=2**-14)
            np.testing.assert_allclose(chooser.kmin[i],
                                       eng.store._abs_kn[sid, i],
                                       rtol=2**-10, atol=2**-14)
    finally:
        shutdown(eng)
