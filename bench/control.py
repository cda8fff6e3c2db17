"""Reads a cell's compared numbers over several seeds in one process: the
program's own, or the control's (the plain reference in the next lower
precision, put in the place of the timed path), to set the check limits.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3 --what control

The benchmark's own runs never run the control. One JSON line per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description="Read a cell's compared numbers over seeds.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--what", choices=("program", "control"), default="control")
    args = ap.parse_args()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.find_cell(args.workload)
    driver = harness.load_module(
        os.path.join(cell.bench, "drivers", f"{cell.traffic['driver']}.py"), "bench_driver")
    timed_path = driver.control(cell.config, cell.traffic) if args.what == "control" else None
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks = harness.run_cell(args.workload, seed, args.seconds, False,
                                          timed_path=timed_path)
        print(json.dumps({"workload": args.workload, "what": args.what, "seed": seed,
                          "correct": result["correct"], "attempted": result["attempted"],
                          "checks": {k: v for k, (v, _) in checks.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
