"""Cells from their files, cut to a size a CPU test run holds."""
import json

import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def cell(name: str, bench_dir=harness.HERE):
    c = harness.load_cell(name, BENCH, bench_dir)
    plan = c.workload["plan"]
    c.traffic["pool_packets"] = 1 << 13
    if "chunk_size" in plan:
        plan["chunk_size"] = 128 if plan.get("fleet") else 512
    return c


def run(c, *, seed=2**31 + 11, seconds=0.5, trace=False, control=False, bench_dir=harness.HERE):
    run, checks, device = harness.run_cell(
        c, seed, seconds, trace, require_tpu=False, control=control,
        bench_dir=bench_dir, started=0.0,
    )
    return harness.result_line(run, checks, device, trace, bench_dir)
