import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell the right step (train_step / prefill / serve decode_step) is
``.lower().compile()``-ed against ShapeDtypeStruct inputs on the production
mesh; we print ``memory_analysis`` (fits-per-device proof) and
``cost_analysis``, and persist a JSON record with the trip-count-scaled HLO
costs (repro.launch.hlo_costing) for §Roofline.

Usage:
  python -m repro.launch.dryrun --arch phi4-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod] [--both]
  python -m repro.launch.dryrun ... --out results/dryrun

The XLA_FLAGS line above must run before ANY other import (jax locks the
device count on first init) — hence its position.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ASSIGNED, SHAPES, get_config, get_shape
from repro.configs.base import ArchConfig, ShapeCfg
from repro.launch import steps as stp
from repro.launch.hlo_costing import analyze
from repro.launch.mesh import make_production_mesh
from repro.models import lm


def runtime_overrides(cfg: ArchConfig, shape: ShapeCfg, mesh) -> ArchConfig:
    """Per-cell execution knobs: grad-accumulation depth targets ~2
    sequences per device per microbatch (activation-memory bound)."""
    rt = cfg.runtime
    if shape.kind == "train":
        from repro.sharding.partition import fsdp_axes, mesh_extent
        gb = shape.global_batch
        per_dev = gb // mesh_extent(mesh, fsdp_axes(mesh))
        # explicit config microbatches win; otherwise target ~2 seqs/device
        nm = rt.microbatches if rt.microbatches > 1 else max(per_dev // 2, 1)
        nm = min(nm, gb)
        while gb % nm:
            nm -= 1
        rt = dataclasses.replace(rt, microbatches=nm)
    return dataclasses.replace(cfg, runtime=rt)


def lower_cell(arch: str, shape_name: str, mesh, *, save_hlo: Optional[str]
               ) -> Dict:
    shape = get_shape(shape_name)
    cfg = runtime_overrides(get_config(arch), shape, mesh)
    n_dev = mesh.devices.size
    rec: Dict = {"arch": arch, "shape": shape_name,
                 "mesh": "x".join(map(str, mesh.devices.shape)),
                 "n_devices": int(n_dev), "kind": shape.kind}
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            jitted, ss, bspec = stp.make_jitted_train_step(
                cfg, mesh, stp.TrainCfg(), shape)
            state = stp.abstract_state(cfg, stp.TrainCfg())
            batch = stp.input_specs(cfg, shape)["batch"]
            lowered = jitted.lower(state, batch)
        elif shape.kind == "prefill":
            jitted = stp.make_jitted_prefill(cfg, mesh, shape)
            params = lm.abstract_params(cfg)
            batch = stp.input_specs(cfg, shape)["batch"]
            lowered = jitted.lower(params, batch)
        else:  # decode
            jitted = stp.make_jitted_decode(cfg, mesh, shape)
            params = lm.abstract_params(cfg)
            spec = stp.input_specs(cfg, shape)
            lowered = jitted.lower(params, spec["cache"], spec["batch"],
                                   spec["length"])
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                     + ma.output_size_in_bytes
                                     + ma.temp_size_in_bytes
                                     - ma.alias_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    rec["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    hlo = compiled.as_text()
    rec["hlo_cost"] = analyze(hlo, n_devices=n_dev)
    if save_hlo:
        os.makedirs(save_hlo, exist_ok=True)
        fn = os.path.join(save_hlo, f"{arch}__{shape_name}__{rec['mesh']}.hlo")
        with open(fn, "w") as f:
            f.write(hlo)
        rec["hlo_file"] = fn
    print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: "
          f"peak={rec['memory']['peak_bytes_per_device']/2**30:.2f} GiB/dev "
          f"xla_flops={rec['xla_cost']['flops']:.3e} "
          f"hlo_flops={rec['hlo_cost']['flops']:.3e} "
          f"coll={rec['hlo_cost']['total_collective_bytes']/2**20:.1f} MiB "
          f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)")
    print("  memory_analysis:", ma)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod AND multi-pod meshes")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--fail-fast", action="store_true")
    args = ap.parse_args()

    archs = ASSIGNED if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = []
    if args.both:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for mesh in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{'x'.join(map(str, mesh.devices.shape))}"
                try:
                    rec = lower_cell(arch, shape, mesh, save_hlo=args.save_hlo)
                    with open(os.path.join(args.out, tag + ".json"), "w") as f:
                        json.dump(rec, f, indent=2)
                except Exception as e:  # noqa: BLE001
                    failures.append((tag, repr(e)))
                    traceback.print_exc()
                    if args.fail_fast:
                        raise
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\n[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()
