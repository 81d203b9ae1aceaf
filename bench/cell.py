"""A benchmark cell, found by name: its entry in ``BENCHMARK.json``, its
configuration file (``configs/<config>.json``), its traffic mix
(``traffic/<traffic>.json``) and the limits of its correctness check
(``limits/<workload>.json``), and the program objects built from them.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Dict

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> Dict[str, Any]:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")


def config(name: str) -> Dict[str, Any]:
    for c in benchmark()["configs"]:
        if c["name"] == name:
            return _json(ROOT / c["file"])
    raise SystemExit(f"no configuration named {name!r} in BENCHMARK.json")


def limits(workload_name: str) -> Dict[str, Any]:
    return _json(BENCH_DIR / "limits" / f"{workload_name}.json")


_RULES_OF_ARCH = ("chunk_size", "importance_rate", "early_layers",
                  "early_rate", "sink_chunks", "recent_chunks")


def _replace(obj, overrides: Dict[str, Any]):
    """``dataclasses.replace`` that descends: a dict given for a field
    that holds a dataclass replaces fields inside it."""
    kw = {k: _replace(getattr(obj, k), v)
          if isinstance(v, dict) and dataclasses.is_dataclass(getattr(obj, k))
          else v for k, v in overrides.items()}
    return dataclasses.replace(obj, **kw)


def arch(conf: Dict[str, Any], smoke: bool = False):
    """The program's ArchConfig for a configuration file, with the
    ``program.overrides`` it states (nested: ``{"moe": {"n_experts":
    8}}`` sets one field of the MoE block) and its LeoAM selection rules
    (``leoam``)."""
    from repro.configs import get_config
    from repro.configs.base import smoke_variant
    prog = conf["program"]
    cfg = _replace(get_config(prog["arch"]), prog["overrides"])
    rules = {k: conf["leoam"][k] for k in _RULES_OF_ARCH}
    cfg = dataclasses.replace(cfg, leoam=dataclasses.replace(cfg.leoam,
                                                             **rules))
    if smoke:
        # the small variant's chunks, at the file's selection budgets,
        # with its last layer past the early ones
        small = smoke_variant(cfg)
        return dataclasses.replace(small, leoam=dataclasses.replace(
            small.leoam, importance_rate=cfg.leoam.importance_rate,
            early_rate=cfg.leoam.early_rate,
            early_layers=min(cfg.leoam.early_layers, small.n_layers - 1)))
    return cfg


def conf_of_arch(cfg, rules: Dict[str, Any]) -> Dict[str, Any]:
    """Configuration-file sizes and selection rules of an ArchConfig,
    under the published keys (for the small variant that tests run, which
    has no file of its own).  Latent attention and experts are reported
    where the ArchConfig has them; ``norm_topk_prob`` is true because the
    program's router renormalises its top-k weights."""
    leoam = dict(rules)
    leoam.update({k: getattr(cfg.leoam, k) for k in _RULES_OF_ARCH})
    conf = {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
        "intermediate_size": cfg.d_ff_dense or cfg.d_ff,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": cfg.tie_embeddings,
        "leoam": leoam,
    }
    if cfg.mla is not None:
        m = cfg.mla
        conf.update(kv_lora_rank=m.kv_lora_rank, q_lora_rank=m.q_lora_rank,
                    qk_nope_head_dim=m.qk_nope_head_dim,
                    qk_rope_head_dim=m.qk_rope_head_dim,
                    v_head_dim=m.v_head_dim)
    if cfg.moe is not None:
        e = cfg.moe
        conf.update(n_routed_experts=e.n_experts,
                    num_experts_per_tok=e.top_k,
                    moe_intermediate_size=e.d_ff_expert,
                    n_shared_experts=e.n_shared,
                    first_k_dense_replace=cfg.first_dense,
                    norm_topk_prob=True, routed_scaling_factor=1.0)
    return conf


def engine_cfg(conf: Dict[str, Any], mix: Dict[str, Any]):
    from repro.serving.engine import EngineCfg
    rules = conf["leoam"]
    return EngineCfg(max_len=int(mix["max_len"]),
                     hot_frac=float(rules["hot_frac"]),
                     selection=rules["selection"], **conf.get("engine", {}))


def pool_chunks(conf: Dict[str, Any], mix: Dict[str, Any]) -> int:
    """Device pool slots per layer: what one round can select at most,
    for every client.  Per sequence at ``max_len``: the budget's chunks
    at the highest rate (one more where the budget splits a chunk), and
    the sink, recent and hot chunks."""
    r = conf["leoam"]
    chunk, max_len = int(r["chunk_size"]), int(mix["max_len"])
    n_chunks = max_len // chunk
    rate = max(float(r["importance_rate"]), float(r["early_rate"]))
    budget = max(chunk, math.ceil(max_len * rate))
    n_hot = max(1, int(n_chunks * float(r["hot_frac"])))
    per_seq = min(n_chunks, -(-budget // chunk) + 1 + int(r["sink_chunks"])
                  + int(r["recent_chunks"]) + n_hot)
    return per_seq * int(mix["clients"])


def scheduler_cfg(conf: Dict[str, Any], mix: Dict[str, Any], chunk: int):
    from repro.serving.scheduler import SchedulerCfg
    return SchedulerCfg(max_active=int(mix["clients"]), chunk=chunk,
                        **conf.get("scheduler", {}))


def peaks(device_kind: str) -> Dict[str, float]:
    table = _json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]
