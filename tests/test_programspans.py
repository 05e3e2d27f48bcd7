"""The benchmark's reader of the program's phase spans
(``benchmarks/chip/programspans.py``): on a hand-made two-chip profile, on
a trace the program writes here on the CPU, and on a trace recorded on a
TPU v5e before the program had phase spans."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import programspans  # noqa: E402
import tracereduce  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "headline-flow.xplane.pb"


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start),
              stats=list(stats.items()))


def profile(host, chips):
    planes = [NS(name="/host:CPU", lines=[
        NS(name="other", events=[ev("dispatch", 0, 1000, chunk=9)]),
        NS(name="python", events=host),
    ])]
    for i, ops in enumerate(chips):
        planes.append(NS(name=f"/device:TPU:{i}", lines=[
            NS(name="XLA Modules", events=[ev("module", 0, 1000)]),
            NS(name="XLA Ops", events=ops),
        ]))
    return NS(planes=planes)


HOST = [
    ev("window", 0, 1000),
    ev("entry.run", 0, 1000),
    ev("ingest", 0, 100, chunk=0),
    ev("h2d", 100, 200, chunk=0),
    ev("dispatch", 200, 500, chunk=0),
    ev("lower_sharding_computation", 250, 400),
    ev("d2h", 500, 700, chunk=0),
    ev("collect", 700, 800, chunk=0),
    ev("ingest", 800, 900, chunk=1),
    ev("dispatch", 900, 1000),  # no chunk: not the program's phase
]
# chip 0 idles in [0,150], [250,550], [600,1000]; chip 1 never; chip 2 is
# not the cell's
CHIPS = [
    [ev("op", 150, 250), ev("op", 550, 600)],
    [ev("op", -50, 1100)],
    [],
]


def test_idle_under_each_phase_averaged_over_the_cells_chips():
    got = programspans.idle_by_phase(profile(HOST, CHIPS), chips=2)
    # ns on chip 0 over the 1000 ns window, halved by the mean over 2 chips
    assert got == pytest.approx({
        "ingest": 100 * 200 / 1000 / 2,
        "h2d": 100 * 50 / 1000 / 2,
        "dispatch": 100 * 250 / 1000 / 2,
        "d2h": 100 * 150 / 1000 / 2,
        "collect": 100 * 100 / 1000 / 2,
    })


def test_shares_add_up_to_device_idle_less_the_uncovered_idle():
    prof = profile(HOST, CHIPS)
    got = programspans.idle_by_phase(prof, chips=2)
    quantities = {
        q: sum(got[p] for p in phases) for q, phases in programspans.QUANTITIES.items()
    }
    assert quantities == pytest.approx(
        {"ingest": 10.0, "transfer": 10.0, "dispatch": 12.5, "collect": 5.0}
    )
    r = tracereduce.reduce_profile(prof, chips=2)
    device_idle = 100 * (1 - r.busy_s / r.window_s)
    uncovered = 100 * 100 / 1000 / 2  # chip 0's [900, 1000]
    assert sum(quantities.values()) == pytest.approx(device_idle - uncovered)


def test_innermost_phase_takes_the_time():
    spans = [ev("dispatch", 0, 100, chunk=0), ev("d2h", 40, 60, chunk=0),
             ev("h2d", 150, 180, chunk=1)]
    assert programspans.phase_segments(spans, 0, 200) == [
        (0, 40, "dispatch"), (40, 60, "d2h"), (60, 100, "dispatch"), (150, 180, "h2d"),
    ]
    holes = [(30, 50), (90, 160)]
    assert programspans.overlap_by_phase(holes, programspans.phase_segments(spans, 0, 200)) \
        == pytest.approx({"ingest": 0, "h2d": 10, "dispatch": 20, "d2h": 10, "collect": 0})


def test_nothing_to_read_is_none(tmp_path, monkeypatch):
    no_phase = [e for e in HOST if not any(k == "chunk" for k, _ in e.stats)]
    assert programspans.idle_by_phase(profile(no_phase, CHIPS), chips=2) is None
    assert programspans.idle_by_phase(profile(HOST, []), chips=1) is None
    assert programspans.idle_by_phase(profile(HOST[1:], CHIPS), chips=2) is None
    assert programspans.idle_share(NS(trace=None, chips=1), "dispatch") is None
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    assert programspans.idle_share(NS(trace=object(), chips=1), "dispatch") is None


def test_recorded_trace_of_a_program_without_phase_spans_reads_none():
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(RECORDED))
    assert programspans.idle_by_phase(prof, chips=1) is None


@pytest.mark.parametrize("quantity", sorted(programspans.QUANTITIES))
def test_each_metric_reads_its_quantity_once_per_trace(quantity, tmp_path, monkeypatch):
    """The reader of ``idle_<quantity>_share`` finds the program's own spans
    in a trace written here; the CPU has no chip, so it reads ``None``, and
    the trace is reduced once for all four readers."""
    from repro.core import bnn, compile_bnn
    from repro.dataplane import execute_stream, lower_program

    params = bnn.init_params(bnn.BnnSpec((16, 8, 4)), jax.random.PRNGKey(1))
    lp = lower_program(compile_bnn([np.asarray(w) for w in params]))
    x = np.random.default_rng(0).integers(0, 2, (300, 16)).astype(np.int32)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            execute_stream(lp, [x], backend="jnp", chunk_size=128)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(str(tracereduce.trace_file(tmp_path)))
    (lo, hi), events = programspans._host_line(prof)
    segments = programspans.phase_segments(events, lo, hi)
    assert {p for _, _, p in segments} == set(programspans.PHASES)

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    programspans._CACHE.clear()
    reader = harness.load_module(harness.HERE, "metrics", f"idle_{quantity}_share")
    assert reader.read(NS(trace=object(), chips=1)) is None  # a CPU trace: no chip

    calls = []

    def reduce(prof, chips):
        calls.append(chips)
        return dict.fromkeys(programspans.PHASES, 1.0)

    monkeypatch.setattr(programspans, "idle_by_phase", reduce)
    programspans._CACHE.clear()
    run = NS(trace=object(), chips=1)
    want = float(len(programspans.QUANTITIES[quantity]))
    assert reader.read(run) == want and reader.read(run) == want
    assert calls == [1]
    programspans._CACHE.clear()
