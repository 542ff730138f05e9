import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh with ShapeDtypeStruct inputs (no allocation), print
memory/cost analysis, and derive the roofline terms from the compiled HLO.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax

from repro.configs import get_config
from repro.launch import cells as cells_mod
from repro.launch.mesh import make_production_mesh
from repro.models.config import SHAPES
from repro.tools import hlo_cost, roofline
from repro.train import steps as steps_mod


def run_cell(arch: str, shape: str, multi_pod: bool, rc_overrides=None,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    rc = SHAPES[shape]
    base_over = cells_mod.OVERRIDES.get((arch, shape))
    if base_over:
        rc = dataclasses.replace(rc, **base_over)
    if rc_overrides:
        rc = dataclasses.replace(rc, **rc_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    bundle = steps_mod.build_step(cfg, rc, mesh)
    with mesh:
        lowered = bundle.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    report = hlo_cost.analyze_compiled(compiled)
    roof = roofline.compute(report, cfg, rc, n_chips)
    out = {
        "arch": arch, "shape": shape, "kind": rc.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "xla_cost_analysis": {
            "flops": cost.get("flops"),
            "bytes accessed": cost.get("bytes accessed"),
        },
        "hlo_cost": report.as_dict(),
        "roofline": roof.as_dict(),
    }
    if verbose:
        _print_cell(out, mem)
    return out


def _print_cell(out, mem):
    r = out["roofline"]
    h = out["hlo_cost"]
    print(f"== {out['arch']} x {out['shape']} on {out['mesh']} "
          f"({out['n_chips']} chips) ==")
    print(f"   lower {out['lower_s']}s  compile {out['compile_s']}s")
    print(f"   memory_analysis: {mem}")
    print(f"   per-device: flops {h['flops']:.3e}  hbm {h['traffic_bytes']:.3e}B  "
          f"collective {h['collective_bytes']:.3e}B  "
          f"({h['n_while']} while loops: {h['trip_counts']})")
    print(f"   collectives: "
          + ", ".join(f"{k}={v:.2e}B" for k, v in h["collectives"].items()))
    print(f"   roofline: compute {r['compute_s']*1e3:.2f}ms  "
          f"memory {r['memory_s']*1e3:.2f}ms  "
          f"collective {r['collective_s']*1e3:.2f}ms  "
          f"-> {r['dominant']}-bound;  "
          f"useful_flops_ratio {r['useful_ratio']:.3f}  "
          f"MFU-bound {r['mfu_bound']:.3f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--set", action="append", default=[],
                    help="RunConfig override k=v (hillclimbing)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    os.makedirs(args.out, exist_ok=True)
    todo = (list(cells_mod.cells()) if args.all
            else [(args.arch, args.shape)])
    failures = []
    for arch, shape in todo:
        tag = "2pod" if args.multi_pod else "1pod"
        suffix = ("_" + "_".join(f"{k}-{v}" for k, v in overrides.items())
                  if overrides else "")
        path = os.path.join(args.out, f"{arch}_{shape}_{tag}{suffix}.json")
        try:
            res = run_cell(arch, shape, args.multi_pod, overrides or None)
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"dry-run OK ({len(todo)} cells, "
          f"{'multi-pod 2x16x16' if args.multi_pod else 'single-pod 16x16'})")


if __name__ == "__main__":
    main()
