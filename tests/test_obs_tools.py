"""Operator tooling: obs_report hardening, obs_diff, bench_history, and
the Prometheus label-escaping pin.

These drive the CLIs in-process (``main(argv)``) so the tests pin exit
codes and messages without subprocess overhead.  The hardening contract:
empty or partial export directories produce a one-line message and a
non-zero exit — never a traceback — and partially-populated rows render
with defaults instead of KeyErrors.
"""
import json
import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
)

import bench_history  # noqa: E402
import check_bench_regression as cbr  # noqa: E402
import obs_diff  # noqa: E402
import obs_report  # noqa: E402

from repro.obs.export import render_prometheus  # noqa: E402
from repro.obs.metrics import MetricsRegistry  # noqa: E402


# ------------------------------------------------------------- prometheus

def test_prometheus_label_values_are_escaped():
    reg = MetricsRegistry()
    reg.counter(
        "dataplane.packets_total",
        tenant='evil"name\\with\nnewline',
    ).inc(3)
    text = render_prometheus(reg)
    # Exposition format: backslash -> \\, quote -> \", newline -> \n.
    assert (
        'tenant="evil\\"name\\\\with\\nnewline"' in text
    )
    assert "\nnewline" not in text.split("} ")[0]  # no raw newline in labels
    for line in text.splitlines():
        assert "\n" not in line  # trivially true, but pins one-line-ness


# ------------------------------------------------------------- obs_report

def _write_metrics(path, rows):
    with open(path, "w") as fh:
        for row in rows:
            fh.write(json.dumps(row) + "\n")


def test_report_empty_dir_message_not_traceback(tmp_path, capsys):
    assert obs_report.main([str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "no *_metrics.jsonl" in err and "export_all" in err


def test_report_missing_explicit_file_exits_cleanly(tmp_path):
    with pytest.raises(SystemExit, match="cannot read metrics file"):
        obs_report.main(["--metrics", str(tmp_path / "nope.jsonl")])
    with pytest.raises(SystemExit, match="cannot read trace file"):
        obs_report.main(["--trace", str(tmp_path / "nope.json")])


def test_report_malformed_inputs_exit_with_location(tmp_path):
    bad = tmp_path / "x_metrics.jsonl"
    bad.write_text('{"name": "a", "type": "counter", "value": 1}\n{oops\n')
    with pytest.raises(SystemExit, match="bad JSONL line"):
        obs_report.main([str(tmp_path)])
    bad.write_text('{"value": 1}\n')
    with pytest.raises(SystemExit, match="missing name/type"):
        obs_report.main([str(tmp_path)])
    trace = tmp_path / "y_trace.json"
    bad.unlink()
    trace.write_text("[1, 2]")
    with pytest.raises(SystemExit, match="not an object"):
        obs_report.main([str(tmp_path)])
    trace.write_text('{"no": "events"}')
    with pytest.raises(SystemExit, match="traceEvents"):
        obs_report.main([str(tmp_path)])


def test_report_partial_rows_render_with_defaults(tmp_path, capsys):
    # Metrics-only dir (no trace), rows missing optional fields.
    _write_metrics(
        tmp_path / "run_metrics.jsonl",
        [
            {"name": "c", "type": "counter"},                # no value
            {"name": "h", "type": "histogram"},              # no count/stats
            {"name": "mt.packets_total", "type": "counter",
             "labels": {"tenant": "t0"}},                    # no value
        ],
    )
    assert obs_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "c = 0" in out and "h" in out and "t0" in out


def test_report_hardware_utilization_section(tmp_path, capsys):
    _write_metrics(
        tmp_path / "run_metrics.jsonl",
        [
            {"name": "roofline.pps_bound", "type": "gauge", "value": 3.3e9,
             "labels": {"path": "packed"}},
            {"name": "roofline.fraction", "type": "gauge", "value": 0.0025,
             "labels": {"path": "packed"}},
            {"name": "roofline.bytes_per_packet", "type": "gauge",
             "value": 248.0, "labels": {"path": "packed"}},
            {"name": "roofline.pps_bound", "type": "gauge", "value": 1.1e9,
             "labels": {"path": "fleet4:packed"}},
            {"name": "dataplane.stream_pps", "type": "gauge", "value": 5e6},
        ],
    )
    assert obs_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "hardware utilization" in out
    assert "packed" in out and "fleet4:packed" in out
    assert "0.25%" in out                      # fraction formatting
    # roofline gauges are grouped, not repeated in the generic gauge dump
    assert "roofline.pps_bound" not in out
    assert "dataplane.stream_pps" in out


def test_report_lowerings_by_span_section(tmp_path, capsys):
    _write_metrics(
        tmp_path / "run_metrics.jsonl",
        [
            {"name": "jax.lowerings_total", "type": "counter", "value": 3,
             "labels": {"span": "dispatch"}},
            {"name": "jax.lowering_seconds_total", "type": "counter",
             "value": 0.25, "labels": {"span": "dispatch"}},
            {"name": "jax.lowerings_total", "type": "counter", "value": 1,
             "labels": {"span": "none"}},
            {"name": "dataplane.chunks_total", "type": "counter", "value": 7},
        ],
    )
    assert obs_report.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    section = out.split("== lowerings by span")[1].split("==\n")[1].split("\n\n")[0]
    rows = [line.split() for line in section.splitlines()[1:]]
    assert rows[0][:2] == ["dispatch", "3"] and rows[1][:2] == ["none", "1"]
    # grouped, not repeated in the generic counter dump
    assert "jax.lowerings_total" not in out
    assert "dataplane.chunks_total = 7" in out


# --------------------------------------------------------------- obs_diff

def _export_dir(tmp_path, name, pps, events=True):
    d = tmp_path / name
    d.mkdir()
    _write_metrics(
        d / "run_metrics.jsonl",
        [
            {"name": "dataplane.stream_pps", "type": "gauge", "value": pps},
            {"name": "dataplane.packets_total", "type": "counter",
             "value": 1000},
        ],
    )
    if events:
        (d / "run_trace.json").write_text(json.dumps({
            "traceEvents": [
                {"ph": "X", "name": "compile:x", "cat": "compile",
                 "ts": 0, "dur": 1000 * pps / 1e6, "tid": 0, "pid": 0},
                {"ph": "X", "name": "execute:x", "cat": "execute",
                 "ts": 2000, "dur": 500, "tid": 0, "pid": 0},
            ]
        }))
    return d


def test_obs_diff_dirs_attributes_phase_movement(tmp_path, capsys):
    a = _export_dir(tmp_path, "a", pps=1e6)
    b = _export_dir(tmp_path, "b", pps=2e6)
    assert obs_diff.main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "phase wall time" in out
    assert "attribution" in out and "compile" in out
    assert "dataplane.stream_pps" in out and "+100.0%" in out


def test_obs_diff_dir_missing_artifacts(tmp_path):
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(SystemExit, match="no \\*_metrics"):
        obs_diff.main([str(empty), str(empty)])


def _bench_payload(tmp_path, name, pps, warmup):
    d = tmp_path / name
    d.mkdir(exist_ok=True)
    path = d / "BENCH_dataplane_bench.json"
    path.write_text(json.dumps({
        "module": "dataplane_bench",
        "seconds": warmup + 1.0,
        "warmup_seconds": warmup,
        "steady_seconds": 1.0,
        "rows": [
            {"name": "dataplane_packed_x", "us_per_call": 10.0,
             "derived": f"pps={pps} warmup_us={warmup * 1e6}",
             "metrics": {"pps": pps, "warmup_us": warmup * 1e6}},
            {"name": "dataplane_packed", "us_per_call": 1.0,
             "derived": "roofline_frac=0.02",
             "metrics": {"roofline_frac": 0.02}},
        ],
    }))
    return path


def test_obs_diff_bench_files_warmup_vs_steady(tmp_path, capsys):
    a = _bench_payload(tmp_path, "a", pps=4e6, warmup=0.1)
    b = _bench_payload(tmp_path, "b", pps=3e6, warmup=0.9)
    assert obs_diff.main(["--bench", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "compile-side (warmup)" in out
    assert "dataplane_packed_x.pps" in out and "-25.0%" in out


def test_obs_diff_vs_baseline(tmp_path, capsys):
    _bench_payload(tmp_path, "cur", pps=4e6, warmup=0.1)
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({
        "budget_env": {k: os.environ.get(k) for k in cbr.BUDGET_ENV},
        "metrics": {
            "dataplane_packed_x.pps": {"value": 5e6,
                                       "higher_is_better": True},
            "dataplane_packed_roofline_frac": {"value": 0.025,
                                               "higher_is_better": True},
        },
    }))
    assert obs_diff.main([
        "--baseline", str(baseline), "--bench-dir", str(tmp_path / "cur"),
    ]) == 0
    out = capsys.readouterr().out
    assert "gated metrics" in out
    assert "dataplane_packed_roofline_frac" in out and "-20.0%" in out
    assert "WARNING" not in out     # budgets match


# ----------------------------------------------- regression-gate flattening

def test_collect_metrics_flattens_roofline_frac(tmp_path):
    _bench_payload(tmp_path, "cur", pps=4e6, warmup=0.1)
    metrics = cbr.collect_metrics(str(tmp_path / "cur"))
    assert metrics["dataplane_packed_roofline_frac"] == {
        "value": 0.02, "higher_is_better": True,
    }
    # and no spurious pps metric from the roofline row itself
    assert "dataplane_packed.pps" not in metrics
    assert "dataplane_packed_x.pps" in metrics


# ------------------------------------------------------------ bench_history

def test_bench_history_appends_jsonl(tmp_path, capsys):
    _bench_payload(tmp_path, "cur", pps=4e6, warmup=0.1)
    hist = tmp_path / "traj.jsonl"
    for note in ("first", "second"):
        assert bench_history.main([
            "--bench-dir", str(tmp_path / "cur"),
            "--history", str(hist), "--note", note,
        ]) == 0
    lines = [json.loads(x) for x in hist.read_text().splitlines()]
    assert [x["note"] for x in lines] == ["first", "second"]
    for line in lines:
        assert line["metrics"]["dataplane_packed_roofline_frac"] == 0.02
        assert line["warmup_seconds"] == 0.1
        assert set(line["budget_env"]) == set(cbr.BUDGET_ENV)
        assert "ts" in line


def test_bench_history_requires_bench_files(tmp_path):
    with pytest.raises(SystemExit, match="no BENCH_"):
        bench_history.main(["--bench-dir", str(tmp_path),
                            "--history", str(tmp_path / "t.jsonl")])
