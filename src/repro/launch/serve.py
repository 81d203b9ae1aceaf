"""Serving entry point: the batched LeoAM engine behind the continuous
batcher, at a model's published widths.

    PYTHONPATH=src python -m repro.launch.serve                 # phi4-mini-3.8b
    PYTHONPATH=src python -m repro.launch.serve --smoke         # small variant

Weights are random, drawn from ``--seed`` (nothing is downloaded); so are
the prompts.  ``--requests`` prompts go through one ``ContinuousBatcher``
over one ``BatchedLeoAMEngine`` and run to completion.  The device is
printed before any work; afterwards the run's serving stats and the
tier-traffic audit.  The exit code is non-zero when any request failed or
the engine counted a failed sequence, an ingest error or a PQ fallback.

``chip_smoke.py`` at the repository root drives the same functions.
"""

from __future__ import annotations

import argparse
import shutil
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.launch.compile_cache import configure_compile_cache
from repro.models import lm
from repro.serving.engine import BatchedLeoAMEngine, EngineCfg
from repro.serving.scheduler import ContinuousBatcher, Request, SchedulerCfg

# (max_len, shortest prompt, longest prompt) when the flags are not given:
# full-width prompts fill one 4096-token prefill bucket and reach past the
# host fraction into the disk tier; the smoke variant keeps CPU runs short
_DEFAULTS = {False: (4096, 3000, 4000), True: (256, 100, 200)}


def device_info() -> Dict[str, object]:
    """The device JAX runs on, as the chip check reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build_model(arch: str, *, smoke: bool, seed: int
                ) -> Tuple[ArchConfig, object]:
    """Config plus random weights from ``seed``.  Initialised under one
    jit, so no leaf's f32 draw sits in device memory beside the finished
    bf16 tree."""
    cfg = get_config(arch, smoke=smoke)
    params = jax.jit(lm.init, static_argnums=0)(cfg,
                                                jax.random.PRNGKey(seed))
    return cfg, jax.block_until_ready(params)


def make_prompts(cfg: ArchConfig, n: int, shortest: int, longest: int,
                 seed: int) -> List[np.ndarray]:
    """``n`` seeded prompts with lengths drawn from [shortest, longest]."""
    rng = np.random.RandomState(seed)
    lengths = rng.randint(shortest, longest + 1, n)
    return [rng.randint(2, cfg.vocab_size, int(L)) for L in lengths]


def serve(cfg: ArchConfig, params, ecfg: EngineCfg,
          prompts: Sequence[np.ndarray], gen: int
          ) -> Tuple[BatchedLeoAMEngine, ContinuousBatcher, List[Request]]:
    """Submit every prompt (``gen`` new tokens each) to a fresh batched
    engine with one sequence slot per prompt, and run them to the end.
    The caller owns the engine: :func:`shutdown` it when done."""
    engine = BatchedLeoAMEngine(cfg, params, ecfg, max_seqs=len(prompts))
    batcher = ContinuousBatcher(
        engine=engine,
        cfg=SchedulerCfg(max_active=len(prompts),
                         chunk=cfg.leoam.chunk_size))
    for rid, p in enumerate(prompts):
        batcher.submit(Request(rid=rid, prompt=p, max_new=gen))
    finished = batcher.run()
    return engine, batcher, finished


def check_run(engine: BatchedLeoAMEngine, finished: Sequence[Request],
              n_requests: int, gen: int) -> List[str]:
    """Everything that says a run did not serve cleanly; empty if clean."""
    problems = []
    if len(finished) != n_requests:
        problems.append(f"{len(finished)} of {n_requests} requests finished")
    for r in finished:
        if r.error is not None:
            problems.append(f"request {r.rid} failed: {r.error}")
        elif len(r.out) != gen:
            problems.append(f"request {r.rid} made {len(r.out)} of {gen} "
                            f"tokens")
        if r.degraded:
            problems.append(f"request {r.rid} was served degraded")
    counters = {"seqs_failed": engine.seqs_failed,
                "ingest_errors": engine.ingest_errors,
                "pq_fallbacks": engine.store.fault_counters["pq_fallbacks"]}
    problems += [f"{k} = {v}" for k, v in counters.items() if v]
    return problems


def shutdown(engine: BatchedLeoAMEngine) -> None:
    """Drain and close the engine's tier store and delete its disk tier
    (a temporary directory the store created)."""
    engine.store.close()
    shutil.rmtree(engine.store._root, ignore_errors=True)


def report(engine: BatchedLeoAMEngine, batcher: ContinuousBatcher,
           finished: Sequence[Request], label: str) -> None:
    """Print per-request results, serving stats and the traffic audit."""
    for r in sorted(finished, key=lambda r: r.rid):
        print(f"request {r.rid}: prompt {len(r.prompt)} tokens, "
              f"{len(r.out)} generated, error={r.error}")
    st = batcher.stats()
    for key in ("p50_ttft_s", "mean_decode_tok_s", "throughput_tok_s"):
        if key in st:
            print(f"{label} {key} = {st[key]}")
    print("tier traffic (MiB):")
    for (src, dst, kind), b in sorted(engine.store.log.bytes.items()):
        print(f"  {src:>6s} -> {dst:6s} [{kind:18s}] {b / 2**20:10.3f}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's small variant (CPU-sized)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, nargs=2,
                    metavar=("SHORTEST", "LONGEST"))
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens per request, the first included")
    ap.add_argument("--max-len", type=int)
    args = ap.parse_args(argv)
    max_len, shortest, longest = _DEFAULTS[args.smoke]
    if args.max_len:
        max_len = args.max_len
    if args.prompt_len:
        shortest, longest = args.prompt_len

    configure_compile_cache()
    dev = device_info()
    label = f"[{dev['platform']}:{dev['kind']} x{dev['count']}]"
    print(f"device {label}")
    cfg, params = build_model(args.arch, smoke=args.smoke, seed=args.seed)
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}, vocab {cfg.vocab_size}, "
          f"{cfg.dtype}")
    prompts = make_prompts(cfg, args.requests, shortest, longest, args.seed)
    engine, batcher, finished = serve(cfg, params,
                                      EngineCfg(max_len=max_len), prompts,
                                      args.gen)
    try:
        report(engine, batcher, finished, label)
        problems = check_run(engine, finished, args.requests, args.gen)
    finally:
        shutdown(engine)
    for p in problems:
        print(f"FAIL: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
