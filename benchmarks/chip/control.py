#!/usr/bin/env python3
"""Read the numbers ``correct`` compares, for the program and for its
control, over several seeds, in one process on the chip.

    python benchmarks/chip/control.py --workload headline-flow \\
        --seeds 1 2 3 [--program-seeds 4 5 ...] --seconds 10 \\
        --control-seconds 2

Each reading is a whole run of the cell (set-up, warm-up, a window at the
cell's own sizes and load, the comparison), first with the program
(``--seconds``) on every seed, then with the control
(``reference.control_forward``, the SIGN tie resolved to 0) in the
program's place (``--control-seconds``: the control verdicts faster than
the program, so a shorter window compares as many packets as a run).
One JSON line per run; the limits are set from these readings (see
PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys

import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    runs = [(s, False) for s in args.seeds + args.program_seeds]
    runs += [(s, True) for s in args.seeds]
    for seed, control in runs:
        cell = harness.load_cell(args.workload, bench)
        seconds = args.control_seconds if control else args.seconds
        run, checks, device = harness.run_cell(cell, seed, seconds, False, control=control)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "side": "control" if control else "program",
            "packets": run.window["packets"],
            "checks": {k: v for k, (v, _) in checks.items()},
            "correct": all(v <= lim for v, lim in checks.values()),
            "kind": device["kind"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
