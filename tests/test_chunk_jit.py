"""The stream path's chunk dispatch is one cached ``jax.jit``.

``executor._run_chunk`` jits :func:`executor._chunk_body` (parse ->
op-table runs -> deparse, or the packed function) once per program,
backend, interpret and scan setting, so a stream lowers its dispatch at
the warm call and never again; ``fleet.fleet_fn`` vmaps the same body.
"""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import bnn, compile_bnn
from repro.dataplane import ExecutionPlan, execute_stream, lower_program
from repro.dataplane.fleet import execute_fleet

# (backend, interpret): the Pallas kernel runs interpreted off the chip
BACKENDS = [("jnp", None), ("packed", None), ("pallas", True)]
IDS = [b for b, _ in BACKENDS]


@pytest.fixture(autouse=True)
def _obs_off():
    obs.disable()
    yield
    obs.disable()


def _model(sizes, seed):
    params = bnn.init_params(bnn.BnnSpec(sizes), jax.random.PRNGKey(seed))
    return params, lower_program(compile_bnn([np.asarray(w) for w in params]))


@pytest.fixture(scope="module")
def small():
    return _model((16, 8, 4), 7)


@pytest.fixture(scope="module")
def headline():
    return _model((32, 64, 32), 14)


def _packets(n, bits, seed=0):
    return np.random.default_rng(seed).integers(0, 2, (n, bits)).astype(np.int32)


def _counters():
    r = obs.registry()
    return (
        r.counter("jax.lowerings_total", span="dispatch").value,
        r.counter("dataplane.chunk_fn_cache_hits_total").value,
        r.counter("dataplane.chunk_fn_cache_misses_total").value,
    )


def _stream(lp, chunks, chunk_size, backend, interpret):
    x = _packets(chunks * chunk_size, lp.input_bits, seed=chunk_size)
    return execute_stream(
        lp, [x], backend=backend, chunk_size=chunk_size, interpret=interpret
    )


@pytest.mark.parametrize("backend,interpret", BACKENDS, ids=IDS)
def test_stream_lowers_its_dispatch_once(small, backend, interpret):
    _, lp = small
    obs.enable(reset=True)
    # chunk sizes no other test uses, so each stream's warm call lowers
    _stream(lp, 1, 71, backend, interpret)
    one, _, _ = _counters()
    assert one >= 1
    _stream(lp, 6, 73, backend, interpret)
    six, _, _ = _counters()
    assert six - one == one  # six chunks lower no more than one
    res = _stream(lp, 6, 73, backend, interpret)
    lowerings, hits, misses = _counters()
    assert res.chunks == 6
    assert lowerings == six  # the same size again: nothing lowers
    # every call hits but the first of the three streams: 2 + 7 + 7
    assert (hits, misses) in ((15, 1), (16, 0))


@pytest.mark.parametrize("backend,interpret", BACKENDS, ids=IDS)
@pytest.mark.parametrize("path", ["stream", "fleet"])
def test_headline_chunk_body_bit_exact(headline, path, backend, interpret):
    """The one chunk body, jitted (stream) and vmapped (fleet), equals the
    oracle on the paper's 32-64-32 headline."""
    params, lp = headline
    x = _packets(300, 32, seed=3)
    want = np.asarray(bnn.forward(params, x))
    if path == "stream":
        res = execute_stream(
            lp, [x], backend=backend, chunk_size=128, collect=True,
            interpret=interpret,
        )
        got = res.outputs
    else:
        plan = ExecutionPlan(
            backend=backend, fleet=2, chunk_size=64, collect=True,
            interpret=interpret,
        )
        res = execute_fleet(lp, [x[:140], x[140:]], plan=plan)
        got = np.concatenate(res.outputs)
    np.testing.assert_array_equal(got, want)
