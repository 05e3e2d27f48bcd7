"""Observability, end to end: a traced multi-tenant run -> report artifacts.

1. **Bit-exactness** — the same stream runs through the fused executor with
   observability OFF and ON; outputs must match element-wise (observation
   never touches data).
2. **Traced run** — with ``repro.obs`` enabled, a three-tenant shared chip
   serves a mixed stream in both scheduling modes (merged, time-sliced)
   and a single-tenant stream runs through ``execute_stream``; the hot
   paths emit spans (``stream:`` > ``compile:`` / ``execute:``) and the
   ``dataplane.*`` / ``mt.*`` metric families.
3. **Export** — metrics land as JSONL + Prometheus text, spans as a Chrome
   Trace Event JSON (load it in ``chrome://tracing`` / Perfetto); the run
   fails unless the trace contains *distinct* compile and execute spans
   and the metrics carry per-tenant queue-delay histograms.
4. **Report** — render the artifacts with::

       python tools/obs_report.py <out-dir>

Run:   PYTHONPATH=src python examples/observe_dataplane.py --out obs_out
Smoke: PYTHONPATH=src python examples/observe_dataplane.py --smoke --out obs_out
(exits non-zero if any bit-exactness or artifact gate fails)
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from repro import compile_cache, obs
from repro.dataplane import (
    FleetSpec,
    TenantSpec,
    build_fleet,
    execute_stream,
    lower_program,
    traffic,
)

_SPEC = FleetSpec(tenants=(
    TenantSpec("ddos", scenario="ddos_burst", shape=(32, 64, 32), weight=2.0,
               seed=0),
    TenantSpec("iot", scenario="iot_telemetry", shape=(16, 32, 8), seed=1),
    TenantSpec("flows", scenario="flow_tuple", shape=(32, 16), seed=2),
))


def main() -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--packets", type=int, default=60_000)
    ap.add_argument("--out", default="obs_out", help="artifact directory")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny budget for CI: same gates, smaller stream",
    )
    args = ap.parse_args()
    n = 6_000 if args.smoke else args.packets
    chunk = min(1 << 12, n)
    failures: list[str] = []

    def gate(ok: bool, what: str) -> None:
        print(("  [ok]   " if ok else "  [FAIL] ") + what)
        if not ok:
            failures.append(what)

    # -- tenants: three independently compiled BNNs sharing one chip, all
    # constructed from the one declarative spec above -----------------------
    fleet = build_fleet(dataclasses.replace(_SPEC, quantum=chunk))

    # -- 1. bit-exactness: observability must not touch the data ----------
    print("== 1. bit-exactness (obs off vs on) ==")
    lp = lower_program(fleet.programs[0])

    def one_stream():
        return execute_stream(
            lp,
            traffic.stream("ddos_burst", n, 32, chunk_size=chunk),
            chunk_size=chunk,
            backend="jnp",
            collect=True,
        )

    obs.disable()
    off = one_stream()
    obs.enable(reset=True)
    on = one_stream()
    gate(
        np.array_equal(off.outputs, on.outputs),
        f"execute_stream outputs identical over {n} packets",
    )

    # -- 2. traced multi-tenant run (obs stays enabled, registry kept) ----
    print("== 2. traced multi-tenant run ==")
    for mode in ("merged", "time_sliced"):
        sched = fleet.scheduler()
        res = sched.run(
            fleet.stream(n, chunk_size=chunk, seed=7),
            mode=mode,
            backend="jnp",
            chunk_size=chunk,
            collect=False,
        )
        print(
            f"  {mode}: {res.packets} packets, "
            f"{res.packets_per_second:.3e} pkt/s, "
            f"warmup {res.warmup_seconds * 1e3:.1f}ms"
        )

    # -- 3. export + artifact gates ---------------------------------------
    print("== 3. export ==")
    paths = obs.export_all(args.out, prefix="example")
    for key in sorted(paths):
        print(f"  {key}: {paths[key]}")

    with open(paths["trace"]) as fh:
        events = json.load(fh)["traceEvents"]
    cats = {e.get("cat") for e in events if e.get("ph") == "X"}
    gate("compile" in cats and "execute" in cats,
         f"trace has distinct compile+execute spans (cats={sorted(cats)})")
    names = {e.get("name") for e in events}
    gate(any(s.startswith("stream:") for s in names),
         "trace has stream-level spans")

    rows = []
    with open(paths["metrics_jsonl"]) as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    qdelay = [
        r for r in rows
        if r["name"] == "mt.queue_delay_seconds"
        and (r.get("labels") or {}).get("tenant")
    ]
    gate(
        {(r["labels"]["tenant"]) for r in qdelay}
        >= {t.name for t in _SPEC.tenants},
        f"per-tenant queue-delay histograms exported ({len(qdelay)} tenants)",
    )
    gate(all(r.get("p50") is not None and r.get("p99") is not None
             for r in qdelay),
         "queue-delay histograms carry p50/p99")
    gate(any(r["name"] == "dataplane.packets_total" for r in rows),
         "dataplane.* metric family exported")

    obs.disable()
    print(
        f"\nrender the report:  python tools/obs_report.py {args.out}"
    )
    if failures:
        print(f"\n{len(failures)} gate(s) FAILED: {failures}")
        return 1
    print("\nall gates passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
