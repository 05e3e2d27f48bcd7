"""Render a human-readable run summary from exported observability files.

Reads the artifacts ``repro.obs.export_all`` writes — a metrics JSONL file
and a Chrome Trace Event JSON — and prints the operator's view of a run:

* **phase decomposition** — wall time per span category (``compile`` vs
  ``execute`` vs ``stream``), the split that turns "the executor is 10,000x
  off the ASIC model" into named phases;
* **top spans** — where the time went, by span name;
* **per-tenant table** — packets / served / dropped / deferred and queue
  delay p50/p99 per tenant (from the ``mt.*`` metric family);
* **lowerings by span** — ``jax.lowerings_total`` and
  ``jax.lowering_seconds_total`` per innermost open span: which step
  recompiled, and what it cost;
* **hardware utilization** — the ``roofline.*`` gauge family grouped per
  compiled path (``packed``, ``jnp``, ``fleetN:...``): analytic packets/s
  bound, measured fraction of it, and bytes per packet
  (``repro.roofline.dataplane``);
* **counters, gauges, histograms** — everything else in the registry.

Stdlib-only (CI's docs job runs it on a tiny traced run).  Usage::

    python tools/obs_report.py [DIR]                 # find obs_* files in DIR
    python tools/obs_report.py --metrics M.jsonl --trace T.json

Exits non-zero (with a one-line message, never a traceback) if no artifact
is found or a file is missing/malformed — a smoke gate, not just a
pretty-printer.  Partial exports are fine: rows missing optional fields
render as zeros/dashes rather than crashing the report.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys


LOWERING_COUNTERS = ("jax.lowerings_total", "jax.lowering_seconds_total")


def load_metrics(path: str) -> list[dict]:
    rows = []
    try:
        fh = open(path)
    except OSError as e:
        raise SystemExit(f"cannot read metrics file {path!r}: {e}")
    with fh:
        for i, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError as e:
                raise SystemExit(f"{path}:{i}: bad JSONL line: {e}")
            if not isinstance(row, dict):
                raise SystemExit(f"{path}:{i}: metric row is not an object")
            if "name" not in row or "type" not in row:
                raise SystemExit(f"{path}:{i}: metric missing name/type")
            rows.append(row)
    return rows


def load_trace(path: str) -> list[dict]:
    try:
        fh = open(path)
    except OSError as e:
        raise SystemExit(f"cannot read trace file {path!r}: {e}")
    with fh:
        try:
            payload = json.load(fh)
        except json.JSONDecodeError as e:
            raise SystemExit(f"{path}: bad trace JSON: {e}")
    if not isinstance(payload, dict):
        raise SystemExit(f"{path}: trace payload is not an object")
    events = payload.get("traceEvents")
    if not isinstance(events, list):
        raise SystemExit(f"{path}: no traceEvents list")
    return [e for e in events if isinstance(e, dict)]


def _fmt_s(seconds: float | None) -> str:
    if seconds is None:
        return "-"
    if seconds >= 1.0:
        return f"{seconds:.2f}s"
    if seconds >= 1e-3:
        return f"{seconds * 1e3:.2f}ms"
    return f"{seconds * 1e6:.1f}us"


def _fmt_g(value: float | None, spec: str) -> str:
    if value is None:
        return "-"
    return format(value, spec)


def _labels(row: dict) -> str:
    labels = row.get("labels") or {}
    if not labels:
        return ""
    return "{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items())) + "}"


def phase_totals(events: list[dict]) -> dict[str, float]:
    """Summed seconds per category, counting only spans not contained in a
    same-category ancestor (mirrors ``Tracer.total_by_category``)."""
    totals: dict[str, float] = {}
    by_tid: dict[int, list[dict]] = {}
    for e in events:
        if e.get("ph") == "X":
            by_tid.setdefault(e.get("tid", 0), []).append(e)
    for evs in by_tid.values():
        for e in evs:
            depth = (e.get("args") or {}).get("depth", 0)
            ts, dur = e.get("ts", 0), e.get("dur", 0)
            contained = any(
                o is not e
                and o.get("cat") == e.get("cat")
                and o.get("ts", 0) <= ts
                and o.get("ts", 0) + o.get("dur", 0) >= ts + dur
                and (o.get("args") or {}).get("depth", 0) < depth
                for o in evs
            )
            if not contained:
                cat = e.get("cat", "span")
                totals[cat] = totals.get(cat, 0.0) + dur / 1e6
    return totals


def span_summary(events: list[dict]) -> list[tuple[str, str, int, float]]:
    """(name, cat, count, total_seconds), sorted by descending total."""
    agg: dict[tuple[str, str], tuple[int, float]] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        key = (e.get("name", "?"), e.get("cat", "span"))
        n, tot = agg.get(key, (0, 0.0))
        agg[key] = (n + 1, tot + e.get("dur", 0) / 1e6)
    rows = [(k[0], k[1], n, tot) for k, (n, tot) in agg.items()]
    rows.sort(key=lambda r: -r[3])
    return rows


def tenant_table(metrics: list[dict]) -> list[dict]:
    """Per-tenant rollup of the ``mt.*`` metric family."""
    tenants: dict[str, dict] = {}

    def cell(name: str) -> dict:
        return tenants.setdefault(
            name,
            {
                "tenant": name, "packets": 0, "served": 0,
                "dropped": 0, "deferred": 0, "slices": 0,
                "qdelay_p50": None, "qdelay_p99": None, "qdelay_n": 0,
            },
        )

    for row in metrics:
        tenant = (row.get("labels") or {}).get("tenant")
        if tenant is None or not row["name"].startswith("mt."):
            continue
        c = cell(tenant)
        if row["name"] == "mt.packets_total":
            c["packets"] = int(row.get("value", 0))
        elif row["name"] == "mt.served_total":
            c["served"] = int(row.get("value", 0))
        elif row["name"] == "mt.dropped_total":
            c["dropped"] = int(row.get("value", 0))
        elif row["name"] == "mt.deferred_total":
            c["deferred"] = int(row.get("value", 0))
        elif row["name"] == "mt.slices_total":
            c["slices"] = int(row.get("value", 0))
        elif row["name"] == "mt.queue_delay_seconds":
            c["qdelay_p50"] = row.get("p50")
            c["qdelay_p99"] = row.get("p99")
            c["qdelay_n"] = row.get("count", 0)
    return [tenants[k] for k in sorted(tenants)]


def lowering_table(metrics: list[dict]) -> list[tuple[str, int, float]]:
    """(span, lowerings, seconds) from the ``jax.lowering*`` counters
    (``repro.obs``'s lowering listener), most seconds first."""
    rows: dict[str, list] = {}
    for row in metrics:
        name = row["name"]
        if row.get("type") != "counter" or name not in LOWERING_COUNTERS:
            continue
        span = (row.get("labels") or {}).get("span", "?")
        r = rows.setdefault(span, [span, 0, 0.0])
        if name == "jax.lowerings_total":
            r[1] = int(row.get("value", 0))
        else:
            r[2] = float(row.get("value", 0.0))
    return sorted((tuple(r) for r in rows.values()), key=lambda r: -r[2])


def roofline_table(metrics: list[dict]) -> list[dict]:
    """Per-path rollup of the ``roofline.*`` gauge family
    (``repro.roofline.dataplane.record``)."""
    paths: dict[str, dict] = {}
    for row in metrics:
        if row.get("type") != "gauge" or not row["name"].startswith(
            "roofline."
        ):
            continue
        path = (row.get("labels") or {}).get("path", "?")
        c = paths.setdefault(path, {"path": path})
        c[row["name"].removeprefix("roofline.")] = row.get("value")
    return [paths[k] for k in sorted(paths)]


def render(metrics: list[dict], events: list[dict]) -> str:
    lines: list[str] = []
    out = lines.append

    if events:
        out("== phase decomposition (trace) ==")
        totals = phase_totals(events)
        width = max(len(c) for c in totals) if totals else 4
        for cat in sorted(totals, key=lambda c: -totals[c]):
            out(f"  {cat:<{width}}  {_fmt_s(totals[cat]):>10}")
        compile_s = totals.get("compile", 0.0)
        execute_s = totals.get("execute", 0.0)
        if execute_s > 0:
            out(
                f"  compile/execute ratio: {compile_s / execute_s:.2f} "
                f"(compile {_fmt_s(compile_s)}, execute {_fmt_s(execute_s)})"
            )
        out("")
        out("== top spans by total time ==")
        out(f"  {'span':<28} {'cat':<8} {'count':>6} {'total':>10} {'avg':>10}")
        for name, cat, n, tot in span_summary(events)[:12]:
            out(
                f"  {name:<28} {cat:<8} {n:>6} {_fmt_s(tot):>10} "
                f"{_fmt_s(tot / n):>10}"
            )
        out("")

    tenants = tenant_table(metrics)
    if tenants:
        out("== per-tenant (mt.*) ==")
        out(
            f"  {'tenant':<14} {'packets':>8} {'served':>8} {'dropped':>8} "
            f"{'deferred':>9} {'slices':>7} {'qdelay p50':>11} {'p99':>10}"
        )
        for c in tenants:
            out(
                f"  {c['tenant']:<14} {c['packets']:>8} {c['served']:>8} "
                f"{c['dropped']:>8} {c['deferred']:>9} {c['slices']:>7} "
                f"{_fmt_s(c['qdelay_p50']):>11} {_fmt_s(c['qdelay_p99']):>10}"
            )
        out("")

    lowerings = lowering_table(metrics)
    if lowerings:
        out("== lowerings by span (jax.lowering*) ==")
        out(f"  {'span':<28} {'count':>6} {'total':>10}")
        for span, n, sec in lowerings:
            out(f"  {span:<28} {n:>6} {_fmt_s(sec):>10}")
        out("")

    roofline = roofline_table(metrics)
    if roofline:
        out("== hardware utilization (roofline.*) ==")
        out(
            f"  {'path':<16} {'pps bound':>12} {'fraction':>10} "
            f"{'bytes/pkt':>10} {'hlo bytes':>11} {'hlo flops':>11}"
        )
        for c in roofline:
            frac = c.get("fraction")
            out(
                f"  {c['path']:<16} "
                f"{_fmt_g(c.get('pps_bound'), '.3e'):>12} "
                f"{_fmt_g(frac, '.2%'):>10} "
                f"{_fmt_g(c.get('bytes_per_packet'), '.1f'):>10} "
                f"{_fmt_g(c.get('hlo_bytes'), '.3e'):>11} "
                f"{_fmt_g(c.get('hlo_flops'), '.3e'):>11}"
            )
        out("")

    counters = [
        m for m in metrics
        if m["type"] == "counter" and m["name"] not in LOWERING_COUNTERS
    ]
    gauges = [
        m for m in metrics
        if m["type"] == "gauge" and not m["name"].startswith("roofline.")
    ]
    histos = [m for m in metrics if m["type"] == "histogram"]
    if counters:
        out("== counters ==")
        for m in counters:
            out(f"  {m['name']}{_labels(m)} = {m.get('value', 0):g}")
        out("")
    if gauges:
        out("== gauges ==")
        for m in gauges:
            out(f"  {m['name']}{_labels(m)} = {m.get('value', 0):g}")
        out("")
    if histos:
        out("== histograms ==")
        out(
            f"  {'histogram':<44} {'count':>7} {'mean':>9} {'p50':>9} "
            f"{'p95':>9} {'p99':>9} {'max':>9}"
        )
        for m in histos:
            label = f"{m['name']}{_labels(m)}"
            out(
                f"  {label:<44} {m.get('count', 0):>7} "
                f"{_fmt_s(m.get('mean')):>9} "
                f"{_fmt_s(m.get('p50')):>9} {_fmt_s(m.get('p95')):>9} "
                f"{_fmt_s(m.get('p99')):>9} {_fmt_s(m.get('max')):>9}"
            )
        out("")
    return "\n".join(lines)


def _find_one(directory: str, suffix: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(directory, f"*{suffix}")))
    return hits[0] if hits else None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "directory", nargs="?", default=".",
        help="directory holding *_metrics.jsonl / *_trace.json "
        "(from repro.obs.export_all)",
    )
    ap.add_argument("--metrics", help="explicit metrics JSONL path")
    ap.add_argument("--trace", help="explicit chrome trace JSON path")
    args = ap.parse_args(argv)

    metrics_path = args.metrics or _find_one(args.directory, "_metrics.jsonl")
    trace_path = args.trace or _find_one(args.directory, "_trace.json")
    if metrics_path is None and trace_path is None:
        print(
            f"no *_metrics.jsonl or *_trace.json under {args.directory!r}; "
            "export with repro.obs.export_all(dir) first",
            file=sys.stderr,
        )
        return 1

    metrics = load_metrics(metrics_path) if metrics_path else []
    events = load_trace(trace_path) if trace_path else []
    print(
        f"obs report: {len(metrics)} metric(s) from "
        f"{metrics_path or '-'}, {len(events)} span(s) from "
        f"{trace_path or '-'}"
    )
    print()
    print(render(metrics, events))
    return 0


if __name__ == "__main__":
    sys.exit(main())
