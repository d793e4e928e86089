#!/usr/bin/env python3
"""Readings of the correctness check: the program, its control and the
planted faults, on one cell at its own size, several seeds in one process.

    python3 bench/control.py --workload isabel.compress --seeds 11 12 13 \
        --seconds 5 --runs sound control altered half unchanged

Each (seed, run) is a whole harness run with a short window; ``sound`` is
the program as it is, the others patch the timed path
(``bench/faults.py``).  One JSON line per (seed, run) on standard output:
``correct`` and every number compared with its limit.  The benchmark's
own runs never run this; its readings set the limits (``PERF.md``).
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--runs", nargs="+", default=["sound", "control"])
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from bench import faults, harness

    for seed in args.seeds:
        for name in args.runs:
            patch = None if name == "sound" else faults.FAULTS[name]
            try:
                out = harness.run(args.workload, seed, args.seconds, False,
                                  time.perf_counter(), patch=patch)
            except harness.NoDevice as e:
                print(f"control: {e}", file=sys.stderr)
                return 2
            print(json.dumps({"seed": seed, "run": name,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
