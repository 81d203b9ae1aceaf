"""Trace reduction, on synthetic events and on a trace recorded on the
CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr


def _trace(ops, spans, window):
    t = tr.Trace(ops={0: ops}, programs={0: ops}, spans=spans,
                 window=window)
    return t


def test_busy_union_idle_share_and_program_time():
    ops = [("a", 0, 10), ("b", 5, 20), ("a", 40, 50), ("c", 95, 120)]
    t = _trace(ops, [], (0, 100))
    assert tr.union(ops) == [(0, 20), (40, 50), (95, 120)]
    assert t.window_s == pytest.approx(100e-9)
    assert tr.busy_s(t) == pytest.approx(35e-9)         # clipped at 100
    assert tr.idle_share(t) == pytest.approx(0.65)
    assert tr.program_s(t, lambda n: n == "a") == pytest.approx(20e-9)


def test_idle_gaps_go_to_the_innermost_span():
    ops = [("x", 0, 10), ("x", 60, 70)]
    spans = [("decode_round", 0, 100), ("select", 20, 40)]
    t = _trace(ops, spans, (0, 100))
    gaps = dict(tr.idle_gaps(t))
    assert gaps["select"] == pytest.approx(20e-9)
    assert gaps["decode_round"] == pytest.approx(60e-9)   # 10-20,40-60,70-100
    assert "no span" not in gaps
    t2 = _trace(ops, [], (0, 100))
    assert dict(tr.idle_gaps(t2))["no span"] == pytest.approx(80e-9)


def test_busy_within_a_span():
    ops = [("p", 0, 30), ("q", 50, 80)]
    t = _trace(ops, [("admit", 20, 60)], (0, 100))
    assert tr.busy_within(t, "admit") == pytest.approx(20e-9)


def test_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("decode_round"):
            for _ in range(3):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("select"):
                time.sleep(0.05)
        time.sleep(0.02)
    jax.profiler.stop_trace()
    t = tr.load(tr.xplane_file(str(tmp_path)), ("decode_round", "select"),
                cpu=True)
    assert 0.07 < t.window_s < 5.0
    busy = tr.busy_s(t)
    assert 0.0 < busy < t.window_s
    assert 0.0 < tr.idle_share(t) < 1.0
    dots = tr.program_s(t, lambda n: n.startswith("dot"))
    assert 0.0 < dots <= busy + 1e-9
    gaps = dict(tr.idle_gaps(t))
    assert gaps["select"] == pytest.approx(0.05, rel=0.5)
    assert gaps.get("no span", 0.0) >= 0.015
    assert {n for n, _, _ in t.spans} == {"decode_round", "select"}
