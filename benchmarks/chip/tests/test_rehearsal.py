"""Each cell built from its files and run through the harness's own
functions on the CPU, at a tiny size, for half a second."""
import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
import tiny


@pytest.fixture(autouse=True)
def own_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")


@pytest.mark.parametrize("name", tiny.CELLS)
def test_cell_runs_and_is_correct(name):
    line = tiny.run(tiny.cell(name))
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in tiny.BENCH["end_to_end"] if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics():
    line = tiny.run(tiny.cell("headline-flow"), trace=True)
    assert line["correct"] is True
    assert line["device"]["window_s"] > 0
    assert line["metrics"]["compiles_in_window.headline-flow"]["value"] == 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/harness.py", "--workload", "headline-flow",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_cli_exits_nonzero_without_a_tpu():
    proc = _cli(harness.ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_cli_exits_nonzero_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _cli(tmp_path)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_a_cell_file_is_found_by_name(tmp_path):
    bench_dir = tmp_path / "chip"
    shutil.copytree(harness.HERE, bench_dir, ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((bench_dir / "workloads" / "headline-flow.json").read_text())
    spec["plan"]["chunk_size"] = 256
    (bench_dir / "workloads" / "headline-flow-256.json").write_text(json.dumps(spec))
    bench = copy.deepcopy(tiny.BENCH)
    next(m for m in bench["end_to_end"] if m["name"] == "pps")["workloads"].append(
        "headline-flow-256")
    c = harness.load_cell("headline-flow-256", bench, bench_dir)
    c.traffic["pool_packets"] = 1 << 13
    line = tiny.run(c, bench_dir=bench_dir)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"pps", "setup_s"}
    with pytest.raises(FileNotFoundError):
        harness.load_cell("no-such-cell", tiny.BENCH, bench_dir)
