"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
Roofline terms come from the dry-run artifacts — see
``python -m repro.launch.roofline``.
"""

from __future__ import annotations

import os
import sys
import traceback

# make `python benchmarks/run.py` work from anywhere: the repo root (for
# the benchmarks package) and src/ (for repro) join sys.path
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_ROOT, os.path.join(_ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> None:
    # measurement hygiene: never produce measured rows with the sync-
    # sanitizer live — its owning-thread/epoch/lock-order checks would be
    # folded into every checked-in baseline number.  The sanitizer's own
    # overhead is measured explicitly by fig13/debug_sync/{on,off}.
    from repro.serving import sanitizer
    if sanitizer.active():
        raise SystemExit(
            "benchmarks/run.py: the sync-sanitizer is active (debug_sync "
            "engine live or REPRO_DEBUG_SYNC=1) — refusing to emit measured "
            "numbers; unset REPRO_DEBUG_SYNC / close debug engines first")
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from benchmarks import (common, engine_audit, fig4_5_overheads,
                            fig7_8_desert, fig10_11_evals, fig13_pipeline,
                            fig14_quality, fig15_latency, fig16_17_breakdown,
                            fig18_19_sensitivity, kernels_micro)
    args = sys.argv[1:]
    if "--smoke" in args:            # cheapest config per fig (CI tier)
        args.remove("--smoke")
        common.set_smoke(True)
    sys.argv = [sys.argv[0]] + args
    print("name,us_per_call,derived")
    modules = [
        ("fig4_5", fig4_5_overheads), ("fig7_8", fig7_8_desert),
        ("fig10_11", fig10_11_evals), ("fig13", fig13_pipeline),
        ("fig14", fig14_quality), ("fig15", fig15_latency),
        ("fig16_17", fig16_17_breakdown), ("fig18_19", fig18_19_sensitivity),
        ("kernels", kernels_micro), ("engine", engine_audit),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failed = []
    for name, mod in modules:
        if only and only not in name:
            continue
        try:
            mod.run()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
