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


def _idle_gaps_by_scan(t, n=10):
    """The reduction as a plain scan: every gap cut at every span edge,
    each piece credited to the shortest span covering its midpoint."""
    from collections import defaultdict
    busy = tr.union(tr.clip(t.ops[min(t.ops)], t.window))
    lo, hi = t.window
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    spans = tr.clip(t.spans, t.window)
    edges = sorted({x for _, s, e in spans for x in (s, e)})
    acc = defaultdict(float)
    for g0, g1 in gaps:
        cuts = [g0] + [x for x in edges if g0 < x < g1] + [g1]
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [(e - s, nm) for nm, s, e in spans if s <= mid < e]
            acc[min(cover)[1] if cover else "no span"] += (b - a) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


@pytest.mark.parametrize("seed", range(4))
def test_idle_gaps_equal_the_plain_scan(seed):
    """Nested rounds of spans (some sharing a length, some cut by the
    window) over scattered ops: the sweep credits every piece as the
    scan does, to the last bit."""
    import random
    rng = random.Random(seed)
    ops, spans, t = [], [], 0
    for _ in range(60):
        r0 = t
        for name in ("select", "fetch", "leoam.sync", "leoam.attend"):
            s = t + rng.randint(0, 40)
            e = s + rng.choice([25, 50, rng.randint(1, 90)])
            spans.append((name, s, e))
            t = e
        spans.append(("decode_round", r0, t + rng.randint(0, 30)))
        t += 40
    for _ in range(400):
        s = rng.randint(0, t)
        ops.append(("x", s, s + rng.randint(1, 30)))
    rng.shuffle(spans)
    t_ = _trace(ops, spans, (rng.randint(0, 200), t - rng.randint(0, 200)))
    assert tr.idle_gaps(t_) == _idle_gaps_by_scan(t_)
    assert tr.idle_gaps(t_, n=3) == _idle_gaps_by_scan(t_, n=3)
