#!/usr/bin/env python3
"""Run one cell several times, each run its own process, and report the
spread of each metric.

    python benchmarks/chip/measure.py --workload headline-flow \\
        --seeds 11 12 13 14 15 16 --seconds 10 [--trace 1] [--out FILE]

Each run is ``harness.py`` exactly as the benchmark's command runs it.
Every result line is printed as it comes (and appended to ``--out``), and
the summary gives each metric's median and its spread: the distance
between the first and third quartiles (``statistics.quantiles(values,
n=4)``) as a share of the median.  This process never imports JAX, so
each run has the chips to itself.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def spread(values) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("inf")


def phases(stderr: str):
    """The run's set-up phases (the harness's ``phases_s`` line), if any."""
    for line in reversed(stderr.splitlines()):
        if line.startswith("phases_s "):
            return json.loads(line.split(" ", 1)[1])
    return None


def run_once(workload: str, seed: int, seconds: int, trace: int, timeout: float):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, str(HERE / "harness.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=timeout,
    )
    wall = time.monotonic() - t0
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        result = None
    return proc, result, wall


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--out", type=Path)
    ap.add_argument("--keep-trace", type=Path, help="copy each traced run's trace here")
    args = ap.parse_args(argv)
    values: dict[str, list] = {}
    bad = 0
    for seed in args.seeds:
        proc, result, wall = run_once(args.workload, seed, args.seconds, args.trace, args.timeout)
        record = {"workload": args.workload, "seed": seed, "trace": args.trace,
                  "rc": proc.returncode, "wall_s": wall, "result": result,
                  "phases_s": phases(proc.stderr)}
        if result is None or proc.returncode != 0 or not result.get("correct"):
            bad += 1
            record["stderr_tail"] = proc.stderr[-4000:]
        print(json.dumps(record), flush=True)
        if args.out:
            args.out.parent.mkdir(parents=True, exist_ok=True)
            with args.out.open("a") as f:
                f.write(json.dumps(record) + "\n")
        if args.keep_trace and args.trace:
            dest = args.keep_trace / f"{args.workload}-{seed}"
            shutil.rmtree(dest, ignore_errors=True)
            shutil.copytree(ROOT / ".chipbench" / "trace", dest)
        for name, m in (result or {}).get("metrics", {}).items():
            values.setdefault(name, []).append(m["value"])
    for name, vals in values.items():
        line = {"workload": args.workload, "metric": name, "n": len(vals),
                "median": statistics.median(vals), "values": vals}
        if len(vals) >= 2:
            line["spread"] = spread(vals)
        print("summary " + json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
