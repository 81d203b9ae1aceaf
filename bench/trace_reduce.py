"""From a profiler trace to device busy time, idle share, program time
and the host spans that explain the idle gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote: the device's
op events (TPU planes' "XLA Ops" line), its program events ("XLA
Modules"), and the harness's host spans (``jax.profiler.TraceAnnotation``
events on the host plane).  The reductions work on plain
``(name, start_ns, end_ns)`` tuples, so they are tested on a trace
recorded on the CPU, whose op events come from the CPU client's threads.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]       # name, start ns, end ns

# host threads whose events are device work when the device is the CPU
CPU_DEVICE_LINES = ("tf_XLAPjRtCpuClient",)


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)  # per device
    programs: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)
    window: Tuple[float, float] = (0.0, 0.0)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def xplane_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, span_names: Iterable[str], *, window_span: str = "window",
         cpu: bool = False) -> Trace:
    """Read ``path`` (an .xplane.pb).  The window is the host span named
    ``window_span``; ``span_names`` are the other host annotations to
    keep.  ``cpu=True`` takes op events from the CPU client's threads."""
    from jax.profiler import ProfileData
    names = set(span_names) | {window_span}
    pd = ProfileData.from_file(path)
    tr = Trace()
    dev = 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    tr.ops.setdefault(idx, []).extend(evs)
                elif line.name == "XLA Modules":
                    tr.programs.setdefault(idx, []).extend(evs)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name in names:
                        tr.spans.append(ev)
                    elif cpu and line.name.startswith(CPU_DEVICE_LINES) \
                            and e.duration_ns > 0 \
                            and not e.name.startswith("Threadpool"):
                        tr.ops.setdefault(dev, []).append(ev)
                        tr.programs.setdefault(dev, []).append(ev)
    win = [ev for ev in tr.spans if ev[0] == window_span]
    if not win:
        raise ValueError(f"trace has no {window_span!r} span")
    tr.window = (win[0][1], win[0][2])
    tr.spans = [ev for ev in tr.spans if ev[0] != window_span]
    return tr


def clip(events: Iterable[Event], window: Tuple[float, float]
         ) -> List[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which some op ran, averaged over the devices traced."""
    if not tr.ops:
        return 0.0
    per = [sum(e - s for s, e in union(clip(evs, tr.window)))
           for evs in tr.ops.values()]
    return sum(per) / len(per) * 1e-9


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_s(tr) / tr.window_s


def program_s(tr: Trace, match) -> float:
    """Device seconds of the programs whose name satisfies ``match``,
    summed over calls, averaged over devices."""
    if not tr.programs:
        return 0.0
    per = [sum(e - s for n, s, e in clip(evs, tr.window) if match(n))
           for evs in tr.programs.values()]
    return sum(per) / len(per) * 1e-9


def busy_within(tr: Trace, span_name: str) -> float:
    """Device busy seconds inside the host spans named ``span_name``."""
    spans = union((n, s, e) for n, s, e in clip(tr.spans, tr.window)
                  if n == span_name)
    if not tr.ops or not spans:
        return 0.0
    total = 0.0
    for evs in tr.ops.values():
        busy = union(clip(evs, tr.window))
        total += _overlap(busy, spans)
    return total / len(tr.ops) * 1e-9


def _overlap(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_gaps(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Device idle time (first device) split by the innermost host span
    open at each idle instant; time under no span is "no span".  The
    ``n`` largest, in seconds."""
    if not tr.ops:
        return []
    busy = union(clip(tr.ops[min(tr.ops)], tr.window))
    lo, hi = tr.window
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = sorted(clip(tr.spans, tr.window), key=lambda ev: ev[1])
    # cut each gap at every span edge, credit each piece to the shortest
    # (innermost) span covering it, by (length, name).  One sweep: the
    # pieces' midpoints only grow, so spans open in start order into a
    # heap and leave it once they have ended.
    acc: Dict[str, float] = defaultdict(float)
    edges = sorted({x for _, s, e in spans for x in (s, e)})
    open_: List[Tuple[float, str, float]] = []      # (length, name, end)
    nxt = 0
    for g0, g1 in gaps:
        inner = edges[bisect.bisect_right(edges, g0):
                      bisect.bisect_left(edges, g1)]
        cuts = [g0] + inner + [g1]
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            while nxt < len(spans) and spans[nxt][1] <= mid:
                nm, s, e = spans[nxt]
                heapq.heappush(open_, (e - s, nm, e))
                nxt += 1
            while open_ and open_[0][2] <= mid:
                heapq.heappop(open_)
            acc[open_[0][1] if open_ else "no span"] += (b - a) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def program_name(name: str) -> str:
    """A program's name without the run-specific id XLA appends."""
    return name.split("(", 1)[0]


def top_programs(tr: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` device programs (first device) that took most time, in
    seconds summed over their calls."""
    if not tr.programs:
        return []
    acc: Dict[str, float] = defaultdict(float)
    for nm, s, e in clip(tr.programs[min(tr.programs)], tr.window):
        acc[program_name(nm)] += (e - s) * 1e-9
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]
