"""Spans and counters of the decode round.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: with a trace
active (``jax.profiler.start_trace`` / ``start_server``) the event lands
on the host plane of the same trace as the device's ops, on the same
clock, so an idle gap on the device can be put down to the host phase
that was running.  While a :class:`Round` is open on the calling thread,
the span also adds its *self time* (its duration less that of the spans
opened inside it) to the round, so nested spans are counted once.

A round holds, per phase in :data:`DECODE_SPANS`, ``<phase>_s`` seconds,
and the backend compiles made on its thread (``compiles``, ``compile_s``,
``compiles_by_phase``: each compile credited to the innermost open span).
The engine merges :meth:`Round.profile` into the round's
``round_profiles`` entry.

A round whose attention layers include expert layers also carries their
dispatch counts (``models.mlp.MOE_COUNTS``), summed over those layers on
the device and read with the round's logits, in the same transfer:

* ``moe_picks_held``: (token, expert) routing picks that land on the
  experts this layer holds (of ``top_k`` per token over all the experts
  the router scores);
* ``moe_rows``: rows the held experts multiplied, empty dispatch slots
  included (the drop-free dispatch gives every held expert one slot per
  token);
* ``moe_experts_hit``: held experts with at least one pick.

A round with no expert layer has none of these keys.

The spans are always on: with no trace active an annotation and a clock
pair cost about a microsecond, against a decode round of a thousand or
more microseconds per layer.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import jax

#: every span of the decode round; ``<name>_s`` keys in a round's profile
DECODE_SPANS = (
    "leoam.round",            # decode_round: fences, retries, round body
    "leoam.fence",            # write-behind ingest fences at round entry
    "leoam.weights",          # picking a layer's weights: a scanned attention
                              # layer's stacked tree and repeat index (its
                              # programs slice on device); the slice of a
                              # scanned recurrent layer's
    "leoam.qkv",              # pre-attention program: norm, Q/K/V, rotary
    "leoam.sync",             # device -> host reads on the decode thread
    "leoam.select.abstracts",  # chunk abstracts: prefetch wait, read, H2D
    "leoam.select.bounds",    # bounds matmul (and PQ scores) dispatch
    "leoam.select.choose",    # per-sequence chunk choice on the host
    "leoam.fetch",            # tier fetch into the device pool
    "leoam.prefetch",         # next layer's speculative prefetch submit
    "leoam.attend",           # chunk ids, their H2D, sparse attend dispatch
    "leoam.append",           # the new token's K/V into the tier store
    "leoam.mlp",              # post-attention program: residual add + MLP
    "leoam.recurrent",        # non-attention layers, per sequence
    "leoam.logits",           # final norm + LM head dispatch
    "leoam.requant",          # sidecar / PQ requant sweep at round end
)

#: phases outside the round body's ``total_s`` (timed from the embedding
#: to the logits): the rest are all inside it
OUTSIDE_TOTAL = ("leoam.round", "leoam.fence", "leoam.requant")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class _Local(threading.local):
    def __init__(self) -> None:
        self.round: Optional[Round] = None
        self.stack: List[span] = []       # open spans of the open round


_local = _Local()


def _on_event(event: str, duration_s: float, **_: Any) -> None:
    if event != _BACKEND_COMPILE:
        return
    rnd = _local.round
    if rnd is None:
        return
    phase = _local.stack[-1].name if _local.stack else "leoam.round"
    rnd.compiles += 1
    rnd.compile_s += duration_s
    rnd.compiles_by_phase[phase] = rnd.compiles_by_phase.get(phase, 0) + 1


# once per process (the import lock makes it once): JAX calls it on the
# compiling thread, so a compile is credited to that thread's open span
jax.monitoring.register_event_duration_secs_listener(_on_event)


class span:
    """``with span("leoam.qkv"): ...`` — see the module docstring."""

    __slots__ = ("name", "_ann", "_t0", "_child_s")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        if _local.round is not None:
            self._child_s = 0.0
            _local.stack.append(self)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        rnd = _local.round
        if rnd is not None:
            dt = time.perf_counter() - self._t0
            stack = _local.stack
            stack.pop()
            rnd.self_s[self.name] = (rnd.self_s.get(self.name, 0.0)
                                     + dt - self._child_s)
            if stack:
                stack[-1]._child_s += dt
        self._ann.__exit__(*exc)


class Round:
    """One decode round on the calling thread: ``with Round() as r:``
    opens ``leoam.round`` and collects the self times of the spans and
    the compiles inside it."""

    def __init__(self) -> None:
        self.self_s: Dict[str, float] = dict.fromkeys(DECODE_SPANS, 0.0)
        self.compiles = 0
        self.compile_s = 0.0
        self.compiles_by_phase: Dict[str, int] = {}
        self._span = span("leoam.round")

    def __enter__(self) -> "Round":
        _local.round = self
        self._span.__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        self._span.__exit__(*exc)
        _local.round = None

    def profile(self) -> Dict[str, Any]:
        """``<phase>_s`` for every phase, ``compiles``, ``compile_s`` and
        ``compiles_by_phase``."""
        out: Dict[str, Any] = {f"{k}_s": v for k, v in self.self_s.items()}
        out["compiles"] = self.compiles
        out["compile_s"] = self.compile_s
        out["compiles_by_phase"] = dict(self.compiles_by_phase)
        return out
