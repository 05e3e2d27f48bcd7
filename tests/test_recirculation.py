"""Networks that one pass cannot hold, compiled into recirculation passes.

A pass runs one neuron group over one fan-in slice within the chip's
element, lane and PHV budgets, re-parsing its input words from the packet;
partial counts, finished sign bits and a later layer's input ride in the
recirculation header.  Every served path runs the passes bit-exact with the
oracle, the benchmark's plain reference and the interpreter; networks that
compile in one pass compile exactly as before.
"""
import hashlib
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import bnn, compile_bnn
from repro.core.interpreter import run_program
from repro.core.pipeline import RMT, ChipSpec, ProgramConstraintError
from repro.core.throughput import report_for_program
from repro.dataplane import execute, execute_stream, lower_program
from repro.dataplane.fabric import SwitchFabric

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import reference  # noqa: E402

# Small enough that a 256-input layer needs passes; deep enough (16
# elements) for one 32-bit word's popcount tree, its ADD and its SIGN.
SMALL = ChipSpec(phv_bits=1024, num_elements=16, max_parallel_ops=32, name="small")
SMALL_SHAPE = (256, 16, 8)


def _weights(shape, seed):
    return reference.make_weights([shape], seed)[0]


def _packets(n, bits, seed):
    return np.random.default_rng(seed).integers(0, 2, (n, bits)).astype(np.int32)


def _assert_within_budgets(prog, chip):
    assert prog.pass_plans and prog.passes == len(prog.pass_plans) > 1
    ranges = [pp.element_range for pp in prog.pass_plans]
    assert ranges[0][0] == 0 and ranges[-1][1] == prog.num_elements
    assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))
    for pp in prog.pass_plans:
        assert pp.num_elements <= chip.num_elements
        assert pp.header_bits <= pp.peak_phv_bits <= chip.phv_bits
    for el in prog.elements:
        el.validate(chip.max_parallel_ops)  # every element within its lanes


@pytest.fixture(scope="module")
def small():
    ws = _weights(SMALL_SHAPE, 16)
    prog = compile_bnn(ws, SMALL)
    x = _packets(300, SMALL_SHAPE[0], 1)
    return ws, prog, lower_program(prog), x


@pytest.fixture(scope="module")
def witnesses(small):
    """The four independent readings of the small program's verdicts."""
    ws, prog, _, x = small
    return {
        "bnn.forward": np.asarray(bnn.forward([np.asarray(w) for w in ws], x)),
        "reference": reference.forward(ws, x),
        "interpreter": np.asarray(run_program(prog, x)),
        "fabric": SwitchFabric.partition(prog, mode="recirculate")
        .run(x, backend="jnp").outputs,
    }


def test_small_chip_compiles_into_passes_within_its_budgets(small):
    ws, prog, lp, _ = small
    _assert_within_budgets(prog, SMALL)
    assert lp.passes == prog.passes
    assert lp.num_elements == lp.passes * lp.elements_per_pass
    # layer 0 re-parses its slice in each of its passes; layer 1 parses none
    first = prog.layer_plans[0].element_range[1]
    assert all(
        pp.parsed for pp in prog.pass_plans if pp.element_range[1] <= first
    )
    assert not prog.pass_plans[-1].parsed


@pytest.mark.parametrize(
    "backend,interpret", [("jnp", None), ("pallas", True), ("packed", None)],
    ids=["jnp", "pallas", "packed"],
)
def test_passes_bit_exact_on_every_backend(small, witnesses, backend, interpret):
    """``execute_stream`` on each backend equals the oracle, the plain
    reference, the interpreter and the recirculating fabric."""
    _, _, lp, x = small
    res = execute_stream(
        lp, [x[:100], x[100:]], backend=backend, chunk_size=128,
        collect=True, interpret=interpret,
    )
    for name, want in witnesses.items():
        np.testing.assert_array_equal(res.outputs, want, err_msg=name)


def test_recirculating_fabric_has_a_hop_per_pass(small):
    _, prog, _, x = small
    fab = SwitchFabric.partition(prog, mode="recirculate")
    assert fab.num_hops == prog.passes
    assert [h.element_range for h in fab.hops] == [
        pp.element_range for pp in prog.pass_plans
    ]
    rate = SMALL.packets_per_second / prog.passes
    assert fab.analytic_report().packets_per_second == pytest.approx(rate)
    assert report_for_program(prog).packets_per_second == pytest.approx(rate)
    scanned = fab.run(x, backend="jnp")
    unrolled = fab.run(x, backend="jnp", scan_hops=False)
    assert scanned.scanned and not unrolled.scanned
    np.testing.assert_array_equal(scanned.outputs, unrolled.outputs)


def test_tie_across_fan_in_slices_fires():
    """Agreement exactly n/2, split unevenly over the passes' slices,
    fires (the paper's SIGN); one less does not."""
    w = np.stack([np.ones(2048, np.int32), np.zeros(2048, np.int32)])
    prog = compile_bnn([w])
    assert prog.passes > 1 and len(prog.pass_plans[0].parsed) < 64
    tie = np.zeros(2048, np.int32)
    tie[:512] = 1        # the first slice all ones,
    tie[1536:2048] = 1   # the last slice all ones, the rest zeros
    below = tie.copy()
    below[0] = 0
    x = np.stack([tie, below])
    want = np.array([[1, 1], [0, 1]])  # 1024 agree / 1024 and 1025 agree
    np.testing.assert_array_equal(reference.forward([w], x), want)
    np.testing.assert_array_equal(np.asarray(run_program(prog, x)), want)
    lp = lower_program(prog)
    for backend in ("jnp", "packed"):
        np.testing.assert_array_equal(execute(lp, x, backend=backend), want)


@pytest.fixture(scope="module")
def wide():
    ws = _weights((2048, 64, 32), 2048)
    return ws, compile_bnn(ws)


def test_2048_64_32_compiles_into_passes_within_rmt(wide):
    _, prog = wide
    assert prog.chip == RMT
    _assert_within_budgets(prog, RMT)
    assert prog.passes == report_for_program(prog).passes
    assert [lp.n_out for lp in prog.layer_plans] == [64, 32]
    assert "passes=" in prog.summary()


def test_2048_64_32_full_width_matches_the_reference(wide):
    ws, prog = wide
    x = _packets(300, 2048, 3)
    got = execute(lower_program(prog), x, backend="jnp")
    np.testing.assert_array_equal(got, reference.forward(ws, x))


def test_a_hidden_layer_no_header_can_carry_still_raises():
    with pytest.raises(ProgramConstraintError, match="PHV"):
        compile_bnn(_weights((32, 4096, 1), 0))


def test_headline_program_is_unchanged():
    """The paper's 32-64-32 compiles to one pass with the fingerprint and
    op-tables it had before programs of passes existed."""
    params = bnn.init_params(bnn.BnnSpec((32, 64, 32)), jax.random.PRNGKey(14))
    prog = compile_bnn([np.asarray(w) for w in params])
    lp = lower_program(prog)
    assert prog.pass_plans is None and lp.passes == 1
    assert prog.fingerprint() == "926b4be73a2d976f91c7eb9cbaedb2ea"
    h = hashlib.sha256()
    for name in (
        "opcode", "dst", "src0", "src1", "imm0", "imm1", "mask", "first_write",
        "rows_per_element", "in_slot_per_bit", "in_shift_per_bit",
        "out_slot_per_bit", "out_shift_per_bit", "opcode_counts",
    ):
        h.update(np.ascontiguousarray(getattr(lp, name)).tobytes())
    assert h.hexdigest() == (
        "34372832c3e6a8ba0e673f7ca96aff2eb22b7bd195f63451068db4b311001c08"
    )
    assert lp.opcode_runs() == (
        (0, 12, (0, 1, 2)), (12, 14, (1, 3, 4)),
        (14, 28, (0, 1, 2)), (28, 30, (1, 3, 4)),
    )
