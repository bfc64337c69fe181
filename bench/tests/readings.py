#!/usr/bin/env python3
"""The two readings a cell's correctness limits are set from, on the chip,
in one process (set-up is paid once):

  * the program's: one harness run per seed, at the cell's own sizes and
    load, with a short window (`--seconds`);
  * the control's: the reference computed in bfloat16, put in the
    program's place, for each control seed, held to the same check.

    python bench/tests/readings.py --workload reddit-hub-ingest \\
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 1

Prints each run's result line and, per control seed, the numbers its
check compares.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def control_checks(workload: str, seed: int, seconds: float = 0.0) -> dict:
    """The cell's check numbers with the bfloat16 reference in the
    program's place."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(harness.ROOT / entry["file"])
    traffic = harness.load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    run = harness.Run(cell, config, traffic, seed, seconds, False, 0.0)
    harness._listen(run)
    run.build_model()
    driver = harness.load_module(BENCH / "drivers" /
                                 f"{traffic['driver']}.py")
    driver.control(run)
    driver.check(run)
    return run.rec["checks"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    for s in filter(None, args.seeds.split(",")):
        rc = harness.main(["--workload", args.workload, "--seed", s,
                           "--seconds", str(args.seconds)])
        if rc:
            return rc
    for s in filter(None, args.control_seeds.split(",")):
        checks = control_checks(args.workload, int(s), args.seconds)
        print("CONTROL", s, json.dumps(checks), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
