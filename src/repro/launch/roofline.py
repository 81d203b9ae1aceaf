"""Roofline analysis from dry-run artifacts (``repro.launch.dryrun``).

Per (arch × shape × mesh) cell:
    compute term    = HLO_FLOPs / (chips × 197e12)
    memory term     = HLO_bytes / (chips × 819e9)
    collective term = collective_bytes / (chips × 50e9)

HLO_FLOPs / HLO_bytes / collective_bytes come from the trip-count-scaled
parse of the compiled SPMD module (repro.launch.hlo_costing) and are
PER-DEVICE, so the "chips ×" denominators cancel against the per-chip
numerators — terms are reported as per-chip seconds.  MODEL_FLOPS uses
6·N·D (train) / 2·N_active·D (inference).  A bf16-correction halves
collective bytes measured on f32 tensors where the model dtype is bf16
(the CPU backend upcasts bf16 dots before the partitioner places
collectives; on TPU those transfers are bf16).

Usage: python -m repro.launch.roofline [--dir results/dryrun] [--csv out]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

from repro.configs import get_config, get_shape

PEAK_FLOPS = 197e12          # bf16 per chip (TPU v5e)
HBM_BW = 819e9               # bytes/s per chip
LINK_BW = 50e9               # bytes/s per link (ICI)

F32_COLLECTIVE_CORRECTION = 0.5   # CPU-backend f32 upcast -> bf16 on TPU


def model_flops(arch: str, shape_name: str, n_devices: int) -> float:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        total = 6.0 * n * tokens
    elif shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        total = 2.0 * n * tokens
    else:
        total = 2.0 * n * shape.global_batch
    return total / n_devices


def analyze_cell(rec: Dict) -> Dict:
    n_dev = rec["n_devices"]
    hc = rec["hlo_cost"]
    flops = hc["flops"]
    mem_bytes = hc["bytes"]
    coll = hc["total_collective_bytes"] * F32_COLLECTIVE_CORRECTION
    t_c = flops / PEAK_FLOPS
    t_m = mem_bytes / HBM_BW
    t_x = coll / LINK_BW
    dominant = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))[1]
    mf = model_flops(rec["arch"], rec["shape"], n_dev)
    bound = max(t_c, t_m, t_x)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / max(flops, 1.0),
        "roofline_fraction": (mf / PEAK_FLOPS) / max(bound, 1e-30),
        "peak_gib": rec["memory"]["peak_bytes_per_device"] / 2**30,
        "collective_bytes": coll,
    }


def suggestion(row: Dict) -> str:
    d = row["dominant"]
    if d == "collective":
        return ("defer/batch gradient reductions; remove per-loop weight "
                "gathers; overlap collectives with compute")
    if d == "memory":
        return ("fuse attention pipeline (Pallas flash/sparse kernels); "
                "raise arithmetic intensity via larger per-step tiles")
    return ("cut non-useful FLOPs: causal-skip attention blocks, lighter "
            "remat policy, avoid recompute of cheap ops")


def load(dir_: str, mesh: Optional[str] = None) -> List[Dict]:
    rows = []
    for fn in sorted(glob.glob(os.path.join(dir_, "*.json"))):
        rec = json.load(open(fn))
        if mesh and rec["mesh"] != mesh:
            continue
        rows.append(analyze_cell(rec))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--csv", default=None)
    args = ap.parse_args()
    rows = load(args.dir, args.mesh)
    hdr = (f"{'arch':24s} {'shape':12s} {'comp_s':>9s} {'mem_s':>9s} "
           f"{'coll_s':>9s} {'dom':>10s} {'useful':>7s} {'roofl%':>7s} "
           f"{'GiB/dev':>8s}")
    print(hdr)
    for r in rows:
        print(f"{r['arch']:24s} {r['shape']:12s} {r['compute_s']:9.3g} "
              f"{r['memory_s']:9.3g} {r['collective_s']:9.3g} "
              f"{r['dominant']:>10s} {r['useful_ratio']:7.2f} "
              f"{100 * r['roofline_fraction']:7.1f} {r['peak_gib']:8.2f}")
    if args.csv:
        import csv
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
        print(f"wrote {args.csv}")


if __name__ == "__main__":
    main()
