"""The per-layer metrics read from the program's own decode-round spans
and counters: each reader on synthetic round profiles, all six in a
traced smoke run, and an idle gap inside a program span booked to it."""
import pytest

import run
import trace_reduce as tr
from test_bench_run import _run

NEW = ("round_sync_ms", "round_weights_ms", "round_dispatch_ms",
       "round_append_ms", "round_untraced_ms", "window_compiles")


@pytest.fixture(autouse=True)
def _harness_for_tests(monkeypatch):
    monkeypatch.setattr(run, "configure_jax", lambda: None)


def _profile(total, compiles=0, **phases):
    """A round profile as the program writes it: every phase's self time
    (0 unless given), the round body's total_s and a compile count."""
    from repro.serving.tracing import DECODE_SPANS
    prof = {f"{p}_s": 0.0 for p in DECODE_SPANS}
    for k, v in phases.items():
        prof[f"leoam.{k}_s"] = v
    prof.update(total_s=total, eval_s=0.0, gather_s=0.0, upload_s=0.0,
                attend_s=total, compiles=compiles, compile_s=0.0,
                compiles_by_phase={})
    return prof


def _read(name, profiles):
    return run.metric_reader(name)(type("Ctx", (), {
        "round_profiles": profiles})())


def test_each_reader_on_synthetic_round_profiles():
    profs = [_profile(1.0, sync=0.2, weights=0.1, qkv=0.05, attend=0.05,
                      mlp=0.1, logits=0.02, append=0.03, round=0.4,
                      fence=0.01, requant=0.3),
             _profile(2.0, sync=0.4, weights=0.3, qkv=0.15, attend=0.05,
                      mlp=0.2, logits=0.08, append=0.07, compiles=3)]
    assert _read("round_sync_ms", profs) == pytest.approx(300.0)
    assert _read("round_weights_ms", profs) == pytest.approx(200.0)
    assert _read("round_dispatch_ms", profs) == pytest.approx(350.0)
    assert _read("round_append_ms", profs) == pytest.approx(50.0)
    # total 1.5 s a round, 0.55 and 1.25 s of it spanned (the round's
    # own, fence and requant self times lie outside total_s)
    assert _read("round_untraced_ms", profs) == pytest.approx(600.0)
    assert _read("window_compiles", profs) == 3


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_without_rounds_or_spans(name):
    assert _read(name, []) is None
    # a program without the spans: the keys it had before them only
    old = {"eval_s": 0.1, "gather_s": 0.0, "upload_s": 0.0,
           "attend_s": 0.9, "total_s": 1.0}
    assert _read(name, [old, dict(old)]) is None


def test_a_traced_smoke_run_reports_the_program_span_metrics():
    res = _run("phi4-decode-8k", 2**31 + 11, trace=True)
    m = res["metrics"]
    assert set(NEW) <= set(m)
    for name in NEW:
        assert m[name]["value"] >= 0.0, name
    assert m["round_sync_ms"]["value"] > 0.0
    assert m["round_weights_ms"]["value"] > 0.0
    assert m["round_dispatch_ms"]["value"] > 0.0
    assert m["round_append_ms"]["value"] > 0.0
    assert m["round_untraced_ms"]["unit"] == "ms"
    assert m["window_compiles"]["unit"] == "1"
    assert res["correct"] is True


def test_idle_gaps_go_to_a_program_span_inside_decode_round():
    ops = [("x", 0, 10), ("x", 60, 70)]
    spans = [("decode_round", 0, 100), ("leoam.round", 2, 98),
             ("leoam.sync", 20, 40), ("leoam.weights", 70, 90)]
    t = tr.Trace(ops={0: ops}, programs={0: ops}, spans=spans,
                 window=(0, 100))
    gaps = dict(tr.idle_gaps(t))
    assert gaps["leoam.sync"] == pytest.approx(20e-9)
    assert gaps["leoam.weights"] == pytest.approx(20e-9)
    assert gaps["leoam.round"] == pytest.approx(38e-9)   # 10-20, 40-60, 90-98
    assert gaps["decode_round"] == pytest.approx(2e-9)   # 98-100
