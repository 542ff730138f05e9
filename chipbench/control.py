#!/usr/bin/env python3
"""Run a cell on several seeds in one process, as it is or with a control
or a fault planted, and print each run's compared numbers.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        --seconds 5 [--plant <control or fault>] [--overrides <json>]

This is how the limits were read: sound runs give the lower reading, the
controls of `faults.CONTROLS` (or, for the training cell, the program's
own bfloat16 weights: `--overrides '{"config": {"training":
{"param_dtype": "bfloat16"}}}'`) the upper one. The benchmark's own runs
never plant or override anything. One JSON line per seed on standard
output.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--plant", default=None,
                    help="a control or fault of faults.py")
    ap.add_argument("--overrides", default="{}",
                    help='JSON {"config": {...}, "traffic": {...}} of keys '
                         "to replace")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import faults, harness
    plant = faults.named(args.plant) if args.plant else None
    for seed in (int(s) for s in args.seeds.split(",")):
        r = harness.run_cell(
            args.workload, seed, args.seconds, False, plant=plant,
            overrides=json.loads(args.overrides))
        print(json.dumps({"seed": seed, "plant": args.plant,
                          "overrides": args.overrides,
                          "correct": r["correct"], "failed": r["failed"],
                          "attempted": r["attempted"],
                          "metrics": r["metrics"], "notes": r["notes"],
                          "checks": r["checks"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
