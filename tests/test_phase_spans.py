"""Phase spans of the served loops, on the profiler's clock.

``execute_stream``, ``execute_fleet`` and ``execute`` open one span of
each phase (``ingest``, ``h2d``, ``dispatch``, ``d2h``, ``collect``) per
chunk, each carrying ``chunk=<k>``.  While a JAX profiler session collects,
``repro.obs`` writes every span into its trace, whether or not the obs
switch is on; with the switch on, lowerings are counted per innermost
span.  None of it may change a verdict.
"""
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import bnn, compile_bnn
from repro.core.pipeline import ChipSpec
from repro.dataplane import ExecutionPlan, execute, execute_stream, lower_program
from repro.dataplane.executor import PHASES
from repro.dataplane.fleet import execute_fleet

SHAPE = (16, 8, 4)


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def model():
    params = bnn.init_params(bnn.BnnSpec(SHAPE), jax.random.PRNGKey(5))
    lp = lower_program(compile_bnn([np.asarray(w) for w in params]))
    return params, lp


def _packets(n, seed=0):
    return np.random.default_rng(seed).integers(0, 2, (n, SHAPE[0])).astype(np.int32)


def _run_stream(lp, x):
    # 470 packets: 4 chunks of 128, the last one short, from slices of 100
    chunks = [x[i : i + 100] for i in range(0, x.shape[0], 100)]
    return execute_stream(lp, chunks, backend="jnp", chunk_size=128, collect=True)


def _run_fleet(lp, x):
    streams = [x[:300], x[300:]]
    plan = ExecutionPlan(backend="jnp", fleet=2, chunk_size=64, collect=True)
    return execute_fleet(lp, streams, plan=plan)


RUNS = {
    "stream": (_run_stream, "stream:execute_stream"),
    "fleet": (_run_fleet, "stream:fleet_run"),
}


def _profiled(tmp_path, fn, *args):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        result = fn(*args)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    profile = ProfileData.from_file(str(path))
    events = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
        for plane in profile.planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
    ]
    return result, events


@pytest.mark.parametrize("path", sorted(RUNS))
def test_every_chunk_has_each_phase_once_in_order(model, tmp_path, path):
    _, lp = model
    run, outer_name = RUNS[path]
    result, events = _profiled(tmp_path, run, lp, _packets(470))
    (outer,) = [e for e in events if e[0] == outer_name]
    phases = sorted(
        (e for e in events if e[0] in PHASES and "chunk" in e[3]),
        key=lambda e: e[1],
    )
    assert phases
    assert all(outer[1] <= e[1] and e[2] <= outer[2] for e in phases)
    warm = [e for e in phases if e[3].get("warm")]
    assert [(e[0], e[3]["chunk"]) for e in warm] == [("dispatch", 0)]
    served = [e for e in phases if not e[3].get("warm")]
    want = [(p, k) for k in range(result.chunks) for p in PHASES]
    # the last pull finds the source dry: an ingest of its own, no chunk
    want.append(("ingest", result.chunks))
    assert [(e[0], e[3]["chunk"]) for e in served] == want
    assert all(a[2] <= b[1] for a, b in zip(served, served[1:]))


@pytest.mark.parametrize("mode", ["obs_off", "obs_on", "profiler_on"])
@pytest.mark.parametrize("path", sorted(RUNS))
def test_outputs_bit_exact_however_observed(model, tmp_path, path, mode):
    params, lp = model
    run, _ = RUNS[path]
    x = _packets(470, seed=1)
    if mode == "obs_on":
        obs.enable(reset=True)
    if mode == "profiler_on":
        result, _ = _profiled(tmp_path, run, lp, x)
    else:
        result = run(lp, x)
    want = np.asarray(bnn.forward(params, x))
    if path == "stream":
        np.testing.assert_array_equal(result.outputs, want)
    else:
        np.testing.assert_array_equal(np.concatenate(result.outputs), want)
    assert result.packets == x.shape[0]


def test_profiler_spans_leave_the_disabled_tracer_empty(model, tmp_path):
    _, lp = model
    obs.reset()
    _, events = _profiled(tmp_path, _run_stream, lp, _packets(300))
    assert any(e[0] == "dispatch" for e in events)
    assert not obs.tracer().records


@pytest.mark.parametrize("n, chunks", [(300, 3), (100, 1)])
def test_execute_opens_each_phase_per_chunk(model, n, chunks):
    params, lp = model
    x = _packets(n, seed=2)
    obs.enable(reset=True)
    got = execute(lp, x, backend="jnp", chunk_size=128)
    np.testing.assert_array_equal(got, np.asarray(bnn.forward(params, x)))
    spans = [
        (r.name, r.args["chunk"]) for r in obs.tracer().records if r.cat == "phase"
    ]
    assert spans == [(p, k) for k in range(chunks) for p in PHASES]


def test_stream_seconds_start_at_ingest(model):
    """A slow source is the loop's time too: ``seconds`` covers each
    chunk from its ``ingest`` on, the warm call left out."""
    _, lp = model
    x = _packets(256)

    def slow():
        for i in range(0, 256, 128):
            time.sleep(0.05)
            yield x[i : i + 128]

    res = execute_stream(lp, slow(), backend="jnp", chunk_size=128)
    assert res.chunks == 2
    assert res.seconds >= 0.1
    assert res.warmup_seconds > 0


def test_lowerings_are_counted_under_dispatch(model):
    _, lp = model
    obs.enable(reset=True)
    # a chunk size no other test uses, so its first call must lower
    execute_stream(lp, [_packets(200)], backend="jnp", chunk_size=97)
    rows = {
        (r["name"], (r.get("labels") or {}).get("span")): r["value"]
        for r in obs.registry().snapshot()
        if r["name"].startswith("jax.lowering")
    }
    assert rows[("jax.lowerings_total", "dispatch")] >= 1
    assert rows[("jax.lowering_seconds_total", "dispatch")] > 0
    obs.disable()
    before = obs.registry().counter("jax.lowerings_total", span="dispatch").value
    execute_stream(lp, [_packets(200)], backend="jnp", chunk_size=95)
    after = obs.registry().counter("jax.lowerings_total", span="dispatch").value
    assert after == before  # the listener goes with the switch


def test_recirculating_stream_lowers_once_and_carries_its_passes(tmp_path):
    """A program of recirculation passes: its stream lowers the dispatch at
    the warm call only, every ``dispatch`` span carries ``passes``, and
    ``dataplane.recirc_passes_total`` counts packets times passes."""
    chip = ChipSpec(phv_bits=1024, num_elements=16, max_parallel_ops=32, name="small")
    params = bnn.init_params(bnn.BnnSpec((256, 16, 8)), jax.random.PRNGKey(6))
    lp = lower_program(compile_bnn([np.asarray(w) for w in params], chip))
    assert lp.passes > 1
    x = np.random.default_rng(4).integers(0, 2, (6 * 64, 256)).astype(np.int32)

    def stream(packets):
        return execute_stream(lp, [packets], backend="jnp", chunk_size=64, collect=True)

    def lowerings():
        return obs.registry().counter("jax.lowerings_total", span="dispatch").value

    obs.enable(reset=True)
    stream(x[:64])
    one = lowerings()
    assert one >= 1
    res, events = _profiled(tmp_path, stream, x)
    assert res.chunks == 6 and lowerings() == one
    np.testing.assert_array_equal(res.outputs, np.asarray(bnn.forward(params, x)))
    dispatch = [e for e in events if e[0] == "dispatch" and "chunk" in e[3]]
    assert len(dispatch) == res.chunks + 1  # the warm call's too
    assert {int(e[3]["passes"]) for e in dispatch} == {lp.passes}
    records = [r for r in obs.tracer().records if r.name == "dispatch"]
    assert {r.args["passes"] for r in records} == {lp.passes}
    total = obs.registry().counter("dataplane.recirc_passes_total").value
    assert total == (64 + x.shape[0]) * lp.passes
