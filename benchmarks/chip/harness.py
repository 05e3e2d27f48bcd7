#!/usr/bin/env python3
"""One cell of the on-chip benchmark, run once.

    python benchmarks/chip/harness.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Set-up builds the cell from its files: ``workloads/<cell>.json`` names a
configuration (``configs/<config>.json``), a traffic mix
(``traffic/<traffic>.json``, made by ``traffic/<generator>.py``), a
driving mode (``modes/<mode>.py``) and the ``ExecutionPlan`` fields.  It
makes the weights and the packet pool from ``--seed``, compiles through
the program's own entry points, and warms every shape the window uses.
The window then drives the entry for ``--seconds``.  Afterwards the timed
outputs are compared with the plain reference (``reference.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result carries
its per-layer metrics, each read by ``metrics/<metric>.py`` from the
reduced trace (``tracereduce.py``); a metric ``<quantity>.<cells>`` is
``metrics/<quantity>.py`` under a bound of its own.  The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, nothing is run and the exit code is 2.
"""
from __future__ import annotations

import time

_T_FIRST = time.clock_gettime(time.CLOCK_BOOTTIME)

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench" / "trace"
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import peaks  # noqa: E402
import reference  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_start() -> float:
    """This process's start on the ``CLOCK_BOOTTIME`` clock, from
    ``/proc/self/stat``; the harness's first statement where that is not
    readable."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return _T_FIRST


def load_json(bench_dir: Path, kind: str, name: str) -> dict:
    path = bench_dir / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(bench_dir: Path, kind: str, name: str):
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    """A cell as its files describe it, with the metrics it reports."""

    name: str
    config: dict
    traffic: dict
    workload: dict
    end_to_end: list
    per_layer: list

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_cell(name: str, bench: dict, bench_dir: Path = HERE) -> Cell:
    """The cell ``name``, found by name under ``bench_dir``; the metrics
    are ``bench``'s (``BENCHMARK.json``) that apply to it."""
    workload = load_json(bench_dir, "workloads", name)
    for entry in bench.get("workloads", []):
        if entry["name"] == name:
            for key in ("config", "traffic", "chips"):
                if entry[key] != workload[key]:
                    raise ValueError(
                        f"cell {name!r}: BENCHMARK.json has {key}={entry[key]!r}, "
                        f"its file {workload[key]!r}"
                    )

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(
        name=name,
        config=load_json(bench_dir, "configs", workload["config"]),
        traffic=load_json(bench_dir, "traffic", workload["traffic"]),
        workload=workload,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


@dataclasses.dataclass
class System:
    """What a mode's driver is given: the cell, its weights and pool
    (``tids``, each packet's tenant, is ``None`` for one model)."""

    cell: Cell
    seed: int
    tenants: list
    weights: list
    tids: object
    bits: object
    annotate: object

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def plan(self) -> dict:
        return self.cell.workload["plan"]


class CompileCounter:
    """Counts programs lowered while ``counting`` is set: every jit cache
    miss lowers, whether the backend then compiles or loads from the
    persistent cache."""

    def __init__(self):
        self.count = 0
        self.counting = False

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.counting and event == LOWERING_EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def configure_jax() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so that only a cell's first run compiles; the
    TPU runtime's logs off unless the environment names a place (they
    default to a fixed path under ``/tmp``); and no metadata-server query
    at the runtime's start unless the environment asks for one (where no
    server answers, the query retries for minutes)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(cell: Cell, require_tpu: bool) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name!r} needs {cell.chips} chips, JAX finds {len(devices)}")
    return devices[: cell.chips]


class Phases:
    """Seconds of each part of set-up on the ``CLOCK_BOOTTIME`` clock,
    from ``started`` on; printed beside a run's result, so that a set-up
    that varies shows where."""

    def __init__(self, started: float):
        self.last = started
        self.seconds = {}

    def mark(self, name: str) -> float:
        now = time.clock_gettime(time.CLOCK_BOOTTIME)
        self.seconds[name] = now - self.last
        self.last = now
        return now


def annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Run:
    """One run's readings, as the metric readers see them."""

    cell: Cell
    setup_s: float
    window: dict
    compiles: int
    peaks: object = None
    chips: int = 1
    trace: object = None
    phases: dict = dataclasses.field(default_factory=dict)


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    require_tpu: bool = True,
    control: bool = False,
    bench_dir: Path = HERE,
    started: float | None = None,
):
    """Set up, warm, measure and check one cell.  Returns ``(run,
    checks, device)``; ``control`` puts the reference's control in the
    program's place."""
    started = process_start() if started is None else started
    phases = Phases(started)
    phases.mark("interpreter")
    configure_jax()
    import jax

    phases.mark("import_jax")
    devices = chips_for(cell, require_tpu)
    phases.mark("devices")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    chip_peaks = peaks.peaks(devices[0].device_kind) if require_tpu else None
    tenants = cell.config["tenants"]
    weights = reference.make_weights([t["shape"] for t in tenants], seed)
    generator = load_module(bench_dir, "traffic", cell.traffic["generator"])
    tids, bits = generator.pool(cell.traffic, tenants, seed)
    phases.mark("pool")
    mode = load_module(bench_dir, "modes", cell.workload["mode"])
    system = System(cell, seed, tenants, weights, tids, bits, annotator(trace))
    driver = mode.Driver(system)
    if control:
        driver.entry = mode.control_entry(driver, reference.control_forward)
    phases.mark("build")
    with CompileCounter() as compiles:
        driver.warm()
        setup_s = phases.mark("warm") - started
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            _start_trace(TRACE_DIR)
        compiles.counting = True
        try:
            with system.annotate("window"):
                window = driver.window(seconds)
        finally:
            compiles.counting = False
            if trace:
                jax.profiler.stop_trace()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": jax.device_count(),
        "memory_peak_bytes": _memory_peak(devices),
    }
    reduced = None
    if trace:
        import tracereduce

        reduced = tracereduce.reduce_dir(TRACE_DIR, len(devices))
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    phases.mark("after_window")
    checks = driver.check(reference.forward)
    phases.mark("check")
    return (
        Run(cell, setup_s, window, compiles.count, chip_peaks, len(devices), reduced,
            phases.seconds),
        checks,
        device,
    )


def _start_trace(path: Path) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(path), profiler_options=options)


def _memory_peak(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max((int(s.get("peak_bytes_in_use", 0)) for s in stats), default=0)


def reader(bench_dir: Path, name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, or for
    ``<quantity>.<cells>`` (one quantity bounded apart for some cells)
    ``metrics/<quantity>.py``."""
    if not (bench_dir / "metrics" / f"{name}.py").is_file():
        name = name.split(".", 1)[0]
    return load_module(bench_dir, "metrics", name)


def metrics_of(run: Run, trace: bool, bench_dir: Path = HERE) -> dict:
    """The cell's end-to-end metrics (``trace`` false) or its per-layer
    metrics, each read by its reader; a per-layer reader that finds
    nothing returns ``None`` and its metric is left out."""
    out = {}
    for m in run.cell.per_layer if trace else run.cell.end_to_end:
        value = reader(bench_dir, m["name"]).read(run)
        if value is None:
            if not trace:
                raise ValueError(f"end-to-end metric {m['name']!r} read nothing")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(
    run: Run, checks: dict, device: dict, trace: bool, bench_dir: Path = HERE
) -> dict:
    correct = all(value <= limit for value, limit in checks.values())
    line = {
        "correct": bool(correct),
        "attempted": int(run.window["attempted"]),
        "failed": int(run.window["attempted"] - run.window["packets"]),
        "metrics": metrics_of(run, trace, bench_dir),
        "device": device,
    }
    if trace and run.trace is not None:
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    started = process_start()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"harness: {ROOT / 'src' / 'repro'} not found; run from a checkout", file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_cell(args.workload, bench)
    try:
        run, checks, device = run_cell(
            cell, args.seed, args.seconds, bool(args.trace), started=started
        )
    except NoChip as e:
        print(f"harness: {e}; nothing was run", file=sys.stderr)
        return 2
    line = result_line(run, checks, device, bool(args.trace))
    print("phases_s " + json.dumps(run.phases), file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
