"""Host spans and counters the harness records around its calls into the
program's layers, for traced runs only.

``Probe.install`` wraps, on the engine instance and its store, the calls
each layer is entered through, in ``jax.profiler.TraceAnnotation`` spans
named after the layer: ``admit`` (engine admission: prefill and ingest),
``decode_round`` (one decode round), ``select`` (chunk selection of one
layer) and ``fetch`` (tier fetch of one layer).  It also counts, inside
the window, the chunks each selection picks and the shape of every call
of the served attend (``_attend_pooled``, or ``_attend_pooled_mla`` for
latent attention), so that the attend's operations and bytes can be
computed from its shapes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List, Tuple

import jax

SPAN_NAMES = ("admit", "decode_round", "select", "fetch")
# the served attends, each with the position of its (B, nmax) slots
ATTEND_FNS = {"_attend_pooled": 2, "_attend_pooled_mla": 3}


class Probe:
    def __init__(self):
        self.recording = False
        self.attend_calls: List[Tuple[int, int]] = []   # (B, nmax)
        self.selected_chunks = 0
        self._undo: List[Callable[[], None]] = []

    def _wrap(self, obj: Any, attr: str, span: str, after=None) -> None:
        orig = getattr(obj, attr)

        @functools.wraps(orig)
        def call(*a, **k):
            with jax.profiler.TraceAnnotation(span):
                out = orig(*a, **k)
            if after is not None and self.recording:
                after(a, k, out)
            return out

        setattr(obj, attr, call)
        self._undo.append(lambda: delattr(obj, attr)
                          if attr in vars(obj) else None)

    def _count_selection(self, a, k, out) -> None:
        sels = out[0]
        self.selected_chunks += sum(len(s) for s in sels.values())

    def install(self, engine: Any) -> None:
        import repro.serving.engine as eng_mod
        self._wrap(engine, "add_sequence", "admit")
        self._wrap(engine, "decode_round", "decode_round")
        self._wrap(engine, "_select_chunks_batched", "select",
                   self._count_selection)
        self._wrap(engine.store, "fetch_chunks_pooled", "fetch")
        for name, slots in ATTEND_FNS.items():
            self._count_attend(eng_mod, name, slots)

    def _count_attend(self, mod: Any, name: str, slots: int) -> None:
        orig = getattr(mod, name)

        def attend(*a, **k):
            if self.recording:
                self.attend_calls.append(tuple(a[slots].shape))
            return orig(*a, **k)

        setattr(mod, name, attend)
        self._undo.append(functools.partial(setattr, mod, name, orig))

    def uninstall(self) -> None:
        for undo in reversed(self._undo):
            undo()
        self._undo.clear()
