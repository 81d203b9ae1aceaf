"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/``) and a
traffic mix (``traffic/``).  One run: check that JAX sees the chips the
cell asks for; make the weights on the device from the seed; build the
batched LeoAM engine behind the continuous batcher; warm up the cell's own
shapes (programs come from the persistent compile cache after the first
run); drive the scheduler in a closed loop for ``--seconds``; then compare
served tokens against the plain reference (``check.py``).  The last line
of standard output is the JSON result.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  Each metric is read by ``metrics/<name>.py``.
"""

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import cell  # noqa: E402
import check  # noqa: E402
import gen_traffic  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402
import weights  # noqa: E402


def configure_jax() -> None:
    """The persistent compile cache (the program's fixed directory, inside
    the checkout, unless JAX_COMPILATION_CACHE_DIR says otherwise), with
    every program cached: the eager decode loop's small programs compile
    in well under JAX's default one-second threshold."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    if _count_event not in _LISTENERS:
        _LISTENERS.append(_count_event)
        jax.monitoring.register_event_duration_secs_listener(_count_event)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILES: List[int] = []        # one entry per backend compile
_LISTENERS: List[Any] = []


def _count_event(name: str, *_a, **_k) -> None:
    if name == _BACKEND_COMPILE:
        _COMPILES.append(1)


def compiles() -> int:
    """Backend compiles so far in this process (cache loads excluded)."""
    return len(_COMPILES)


def device_check(chips: int) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Loop:
    """A closed loop of clients over the continuous batcher: each client
    keeps one request in flight and sends its next when it completes.
    Stamps every output token with the host clock."""

    def __init__(self, batcher, specs, clients: int):
        self.batcher = batcher
        self.specs = specs
        self.clients = clients
        self.reqs: Dict[int, Any] = {}
        self.stamps: Dict[int, List[float]] = {}
        self.rounds: List[tuple] = []          # (t_start, t_end, batch)
        self.sending = True
        self.completed: List[Any] = []
        self._rid = 0
        self._n_fin = 0
        self._open: Dict[int, Any] = {}

    def send(self, spec) -> None:
        from repro.serving.scheduler import Request
        r = Request(rid=self._rid, prompt=spec.prompt, max_new=spec.max_new)
        self._rid += 1
        self.reqs[r.rid] = self._open[r.rid] = r
        self.stamps[r.rid] = []
        self.batcher.submit(r)

    def start(self) -> None:
        for _ in range(self.clients):
            self.send(next(self.specs))

    def step(self) -> None:
        t_start = time.perf_counter()
        self.batcher.step()
        t = time.perf_counter()
        decoded = 0
        for rid, r in list(self._open.items()):
            ts = self.stamps[rid]
            for j in range(len(ts), len(r.out)):
                if j == 0:
                    ts.append(r.t_first if r.t_first is not None else t)
                else:
                    ts.append(t)
                    decoded += 1
        self.rounds.append((t_start, t, decoded))
        fin = self.batcher.finished[self._n_fin:]
        self._n_fin = len(self.batcher.finished)
        for r in fin:
            self._open.pop(r.rid, None)
            self.completed.append(r)
            if self.sending:
                self.send(next(self.specs))

    @property
    def decode_rounds(self) -> int:
        return sum(1 for *_, n in self.rounds if n)


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: Dict[str, Any], workload: str, trace: bool
               ) -> List[Dict[str, Any]]:
    """The metrics this cell reports in this kind of run."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def warm_buckets(engine, lengths: List[int]) -> List[int]:
    """One prompt length per prefill bucket the mix's lengths fall in."""
    out: Dict[int, int] = {}
    for L in sorted(lengths):
        out.setdefault(engine._bucket_len(L), L)
    return sorted(out.values())


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        control: Optional[str] = None, smoke: bool = False,
        mix: Optional[Dict[str, Any]] = None, need_chip: bool = True,
        limits: Optional[Dict[str, float]] = None,
        rounds: Optional[int] = None) -> Dict[str, Any]:
    """One run of a cell; returns the result object.  With ``control``
    (``"fp8"``) the check compares the control's tokens in the program's
    place.  ``smoke``, ``mix``, ``need_chip``, ``limits`` and ``rounds``
    let tests drive the same code at a small size on the CPU; with
    ``rounds`` the window closes after that many decode rounds instead of
    after ``seconds``, so that what a test serves does not depend on the
    speed of the CPU."""
    bench = cell.benchmark()
    w = cell.workload(workload)
    conf = cell.config(w["config"])
    mix = mix or gen_traffic.load(w["traffic"])
    configure_jax()
    import jax
    import numpy as np
    from repro.launch.serve import shutdown
    from repro.models import lm
    from repro.core.tiers import AccessTable
    from repro.serving.engine import BatchedLeoAMEngine
    from repro.serving.scheduler import ContinuousBatcher, Request

    device = (device_check(int(w["chips"])) if need_chip else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": len(jax.devices())})
    peaks = cell.peaks(device["kind"]) if need_chip else \
        {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    if limits is None:
        limits = {name: float(v["limit"])
                  for name, v in cell.limits(workload).items()}

    cfg = cell.arch(conf, smoke=smoke)
    if smoke:
        conf = dict(conf, **cell.conf_of_arch(cfg, conf["leoam"]))
    decay = {f.name: f.default for f in dataclasses.fields(AccessTable)}
    if decay["decay"] != conf["leoam"]["hot_decay"]:
        raise SystemExit(f"bench: the program's hot-chunk decay is "
                         f"{decay['decay']}, the configuration states "
                         f"{conf['leoam']['hot_decay']}")
    layout = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)))
    params = weights.make(layout, seed)
    ecfg = cell.engine_cfg(conf, mix)
    scfg = cell.scheduler_cfg(conf, mix, cfg.leoam.chunk_size)
    clients = int(mix["clients"])
    engine = BatchedLeoAMEngine(cfg, params, ecfg, max_seqs=clients,
                                device_chunk_budget=cell.pool_chunks(conf,
                                                                     mix))
    batcher = ContinuousBatcher(engine=engine, cfg=scfg)

    # ---- set-up: warm the cell's own shapes ----
    if mix["measure"] == "serve":
        lengths = gen_traffic.length_set(mix["prompt"], int(mix["set_size"]))
        wrng = gen_traffic.rng_for(seed, 4)
        for i, L in enumerate(warm_buckets(engine, lengths)):
            batcher.submit(Request(
                rid=-1 - i, prompt=wrng.integers(2, cfg.vocab_size, L),
                max_new=2))
        batcher.run()
        batcher.finished.clear()
    loop = Loop(batcher, gen_traffic.stream(mix, cfg.vocab_size, seed),
                clients)
    loop.start()
    if mix["measure"] == "decode":
        warm = int(mix.get("warm_rounds", 4))
        while loop.decode_rounds < warm:
            loop.step()
    else:
        lead = int(mix.get("lead_in_requests", clients))
        while len(loop.completed) < lead:
            loop.step()

    probe = None
    tdir = None
    if trace:
        probe = spans.Probe()
        probe.install(engine)
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tdir)

    # ---- the measured window ----
    n_rounds0 = len(engine.round_profiles)
    ps0 = engine.pool_stats()
    log0 = dict(engine.store.log.bytes)
    n_rounds_loop0 = len(loop.rounds)
    t_setup = time.perf_counter()
    setup_s = t_setup - _T_START
    compiles0 = compiles()
    if probe is not None:
        probe.recording = True
    last_round = None if rounds is None else loop.decode_rounds + rounds
    with jax.profiler.TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            loop.step()
            if last_round is None:
                if time.perf_counter() - t0 >= seconds:
                    break
            elif loop.decode_rounds >= last_round:
                break
        t1 = time.perf_counter()
    if probe is not None:
        probe.recording = False
        jax.profiler.stop_trace()
    compiles_in_window = compiles() - compiles0
    round_profiles = engine.round_profiles[n_rounds0:]
    ps1 = engine.pool_stats()
    disk_host = sum(v - log0.get(k, 0.0)
                    for k, v in engine.store.log.bytes.items()
                    if k[0] == "disk" and k[1] == "host"
                    and k[2] in ("kv", "kv_shared"))
    win_rounds = loop.rounds[n_rounds_loop0:]

    # requests sent in the window get their first token, however late
    loop.sending = False
    late = [r for r in loop.reqs.values()
            if t0 <= r.t_submit < t1 and r.t_first is None]
    t_wait = time.perf_counter()
    while any(r.t_first is None and r.error is None for r in late) \
            and time.perf_counter() - t_wait < 60.0:
        loop.step()
    mem = jax.devices()[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    ctx = SimpleNamespace(
        conf=conf, mix=mix, peaks=peaks, chunk=cfg.leoam.chunk_size,
        stamps=loop.stamps, t0=t0, t1=t1, setup_s=setup_s,
        submit={r.rid: r.t_submit for r in loop.reqs.values()},
        first={r.rid: r.t_first for r in loop.reqs.values()},
        rounds=win_rounds, round_profiles=round_profiles,
        pool_hits=ps1["hits"] - ps0["hits"],
        pool_misses=ps1["misses"] - ps0["misses"],
        disk_host_billed=disk_host,
        billed_per_chunk=engine.store._disk_read_bytes(),
        chunk_bytes=engine.store.chunk_bytes,
        decode_tokens=sum(n for *_, n in win_rounds),
        attend_calls=probe.attend_calls if probe else [],
        selected_chunks=probe.selected_chunks if probe else 0,
        trace=None)
    breakdown = None
    if trace:
        probe.uninstall()
        tr = trace_reduce.load(trace_reduce.xplane_file(tdir),
                               spans.SPAN_NAMES)
        shutil.rmtree(tdir, ignore_errors=True)
        ctx.trace = tr
        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": [list(x) for x in
                                    trace_reduce.top_programs(tr)],
                     "idle_gaps": [list(x) for x in
                                   trace_reduce.idle_gaps(tr)]}
    device["memory_peak_bytes"] = memory_peak

    metrics = {}
    for m in metrics_of(bench, workload, trace):
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- correctness, once the window has closed ----
    in_window = [r for r in loop.reqs.values()
                 if any(t0 < t <= t1 for t in loop.stamps[r.rid])
                 or t0 <= r.t_submit < t1]
    finished_ok = [r for r in loop.completed if r.error is None]
    if mix["measure"] == "decode":
        # sessions are still decoding at the close: each has served
        # every token it was due so far
        pool = [r for r in loop.reqs.values() if r.error is None and r.out]
    else:
        pool = finished_ok
    chosen = check.sample(pool, int(mix["check_sample"]), seed)
    counters = {"seqs_failed": engine.seqs_failed,
                "ingest_errors": engine.ingest_errors,
                "pq_fallbacks": engine.store.fault_counters["pq_fallbacks"]}
    problems = [f"{k} = {v}" for k, v in counters.items() if v]
    problems += [f"request {r.rid} failed: {r.error}"
                 for r in loop.reqs.values() if r.error is not None]
    problems += [f"request {r.rid} was served degraded"
                 for r in loop.reqs.values() if r.degraded]
    failed = sum(1 for r in in_window if r.error is not None or r.degraded)
    samples = [(np.asarray(r.prompt), list(r.out)) for r in chosen]
    written = sum(v for k, v in engine.store.log.bytes.items()
                  if k[0] == "host" and k[1] == "disk")
    t_close = time.perf_counter()
    shutdown(engine)
    del engine, batcher, loop
    gc.collect()
    t_ref = time.perf_counter()
    modes = ("f32", control) if control else ("f32",)
    per_token: Dict[str, List[float]] = {}
    for prompt, out in samples:
        g = check.gaps(conf, params, prompt, out, int(mix["max_len"]), modes)
        for k, v in g.items():
            per_token.setdefault(k, []).extend(float(x) for x in v)
        print(f"bench: reference over {len(prompt)} + {len(out)} tokens, "
              f"{time.perf_counter() - t_ref:.1f} s since shutdown",
              file=sys.stderr)
    numbers = {k: check.numbers(v) for k, v in per_token.items()}
    for k, v in per_token.items():
        print(f"bench: {k} gaps over {len(v)} tokens: max {max(v):.4f} "
              f"mean {np.mean(v):.4f} median {np.median(v):.4f} "
              f"share over 0.1 {np.mean(np.asarray(v) > 0.1):.4f}",
              file=sys.stderr)
    print(f"bench: {compiles_in_window} compiles in the window; "
          f"{written / 1e9:.3f} GB written host->disk by the store",
          file=sys.stderr)
    print(f"bench: setup {setup_s:.1f} s, window {t1 - t0:.1f} s, "
          f"engine shutdown {t_ref - t_close:.1f} s, reference "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    for p in problems:
        print(f"bench: {p}", file=sys.stderr)
    if control:
        print(f"bench: the {control} control in the program's place; the "
              f"program's own numbers {numbers['served']}", file=sys.stderr)
    compared = numbers[control if control else "served"] if samples else {}
    n_tok = len(per_token.get("served", []))
    checks = {name: {"value": compared.get(name, float("inf")),
                     "limit": limits[name]} for name in check.NUMBERS}
    checks["tokens_compared"] = {"value": n_tok, "limit": 1}
    checks["failed_requests"] = {"value": len(problems), "limit": 0}
    correct = (not problems and n_tok >= 1
               and all(checks[n]["value"] <= limits[n] for n in check.NUMBERS))
    result = {"correct": bool(correct), "attempted": len(in_window),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        result["readings"] = {"program": numbers["served"],
                              control: numbers[control]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None,
                    help="compare the control (the reference one precision "
                    "step down) in the program's place; it has to come out "
                    "not correct")
    args = ap.parse_args(argv)
    res = run(args.workload, args.seed, args.seconds, bool(args.trace),
              control=args.control)
    for name, c in res["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
