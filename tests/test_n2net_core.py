"""The paper's core claims: compiler + interpreter vs the BNN oracle,
Table 1, the §3 ablation, and the headline throughput example."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # environment without hypothesis: deterministic stub
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import bnn, bitops, compile_bnn, run_program, throughput
from repro.core.pipeline import (
    RMT,
    RMT_NATIVE_POPCNT,
    ProgramConstraintError,
    elements_for_neuron_group,
    max_parallel_neurons,
)

settings.register_profile("ci", max_examples=15, deadline=None)
settings.load_profile("ci")

TABLE1_WIDTHS = (16, 32, 64, 128, 256, 512, 1024, 2048)
TABLE1_PARALLEL = (128, 64, 32, 16, 8, 4, 2, 1)
TABLE1_ELEMENTS = (12, 14, 16, 18, 20, 22, 24, 25)


def test_table1_parallel_neurons():
    got = [max_parallel_neurons(n) for n in TABLE1_WIDTHS]
    assert tuple(got) == TABLE1_PARALLEL


def test_table1_elements():
    got = [
        elements_for_neuron_group(n, p)
        for n, p in zip(TABLE1_WIDTHS, TABLE1_PARALLEL)
    ]
    assert tuple(got) == TABLE1_ELEMENTS


def test_single_neuron_formula():
    # paper: "3 + 2log2(N) elements to implement a single neuron"
    for n in TABLE1_WIDTHS:
        assert elements_for_neuron_group(n, 1) == 3 + 2 * int(np.log2(n))


def test_native_popcnt_range_is_5_to_10():
    # Paper §3 recomputes Table 1's operating points (Table-1 parallelism:
    # N=2048 stays single-neuron, so no folding element) with the POPCNT
    # primitive: 12-25 becomes 5-10.
    got = [
        elements_for_neuron_group(
            n, max_parallel_neurons(n, RMT), RMT_NATIVE_POPCNT
        )
        for n in TABLE1_WIDTHS
    ]
    assert min(got) == 5 and max(got) == 10, got  # paper §3: "a 5-10 range"


def test_native_popcnt_doubles_parallelism():
    for n in TABLE1_WIDTHS:
        assert max_parallel_neurons(n, RMT_NATIVE_POPCNT) == 2 * max_parallel_neurons(n, RMT)


def _random_model(layer_sizes, seed):
    spec = bnn.BnnSpec(tuple(layer_sizes))
    params = bnn.init_params(spec, jax.random.PRNGKey(seed))
    x = jax.random.bernoulli(
        jax.random.PRNGKey(seed + 1), 0.5, (16, layer_sizes[0])
    ).astype(jnp.int32)
    return spec, params, x


@given(
    st.lists(st.integers(2, 96), min_size=2, max_size=4),
    st.integers(0, 10_000),
)
def test_interpreter_matches_oracle(layer_sizes, seed):
    spec, params, x = _random_model(layer_sizes, seed)
    prog = compile_bnn([np.asarray(w) for w in params])
    got = run_program(prog, x)
    want = bnn.forward(params, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(
    st.lists(st.integers(2, 96), min_size=2, max_size=3),
    st.integers(0, 10_000),
)
def test_native_popcnt_interpreter_matches_oracle(layer_sizes, seed):
    spec, params, x = _random_model(layer_sizes, seed)
    prog = compile_bnn([np.asarray(w) for w in params], RMT_NATIVE_POPCNT)
    got = run_program(prog, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(bnn.forward(params, x)))


def test_oracle_identities(rng_key):
    """XNOR-popcount == ±1 arithmetic == packed HAKMEM arithmetic."""
    spec = bnn.BnnSpec((48, 32, 10))
    params = bnn.init_params(spec, rng_key)
    x = jax.random.bernoulli(jax.random.PRNGKey(9), 0.5, (32, 48)).astype(jnp.int32)
    base = bnn.forward(params, x)
    pm1 = bnn.forward_pm1(params, bitops.bits_to_sign(x))
    np.testing.assert_array_equal(np.asarray(2 * base - 1), np.asarray(pm1, np.int64))
    np.testing.assert_array_equal(
        np.asarray(bnn.packed_forward(params, x)), np.asarray(base)
    )


def test_headline_example_single_pass():
    """Paper: 960M two-layer BNNs/s — 32b activations, layers of 64 and 32."""
    spec = bnn.BnnSpec((32, 64, 32))
    params = bnn.init_params(spec, jax.random.PRNGKey(0))
    prog = compile_bnn([np.asarray(w) for w in params])
    assert prog.num_elements == 30          # 14 + 16, <= 32
    assert prog.passes == 1
    rep = throughput.report_for_program(prog)
    assert rep.networks_per_second == pytest.approx(960e6)
    # analytic model agrees with the compiled program
    assert throughput.analytic_elements(spec) == 30


def test_max_activation_vector_is_2048():
    """Paper: max activation length 2048 (duplication halves the 512B PHV)."""
    spec = bnn.BnnSpec((2048, 1))
    params = bnn.init_params(spec, jax.random.PRNGKey(0))
    prog = compile_bnn([np.asarray(w) for w in params])
    assert prog.peak_phv_bits == 4096       # exactly full PHV
    assert prog.num_elements == 25          # Table 1 right edge

    # Past 2048 bits one pass no longer holds the neuron: it recirculates,
    # each pass over a fan-in slice within the PHV (paper §2-3).
    big = bnn.BnnSpec((4096, 1))
    wide = compile_bnn([np.asarray(w) for w in bnn.init_params(big, jax.random.PRNGKey(0))])
    assert wide.passes > 1 and wide.pass_plans is not None
    assert max(pp.peak_phv_bits for pp in wide.pass_plans) <= 4096
    # A hidden layer wider than the PHV cannot ride in the recirculation
    # header: that network the chip cannot run.
    bad = bnn.init_params(bnn.BnnSpec((32, 8192, 1)), jax.random.PRNGKey(0))
    with pytest.raises(ProgramConstraintError):
        compile_bnn([np.asarray(w) for w in bad])


def test_neuron_rate_scales_with_parallelism():
    assert throughput.neuron_rate(2048) == pytest.approx(960e6)
    assert throughput.neuron_rate(32) == pytest.approx(960e6 * 64)


def test_recirculation_halves_throughput():
    """Networks too big for 32 elements recirculate; pps divides by passes."""
    spec = bnn.BnnSpec((128, 128, 64, 32))
    params = bnn.init_params(spec, jax.random.PRNGKey(1))
    prog = compile_bnn([np.asarray(w) for w in params])
    rep = throughput.report_for_program(prog)
    assert rep.passes == -(-prog.num_elements // 32)
    assert rep.packets_per_second == pytest.approx(960e6 / rep.passes)


@given(
    st.lists(st.integers(2, 64), min_size=2, max_size=4),
    st.integers(0, 10_000),
)
def test_compiled_programs_respect_chip_constraints(layer_sizes, seed):
    """PHV/element invariants hold for arbitrary model shapes."""
    spec, params, _ = _random_model(layer_sizes, seed)
    prog = compile_bnn([np.asarray(w) for w in params])
    assert prog.peak_phv_bits <= prog.chip.phv_bits
    for el in prog.elements:
        el.validate(prog.chip.max_parallel_ops)  # raises on violation
        dsts = [op.dst.fid for op in el.ops]
        assert len(dsts) == len(set(dsts))       # one write per field


def test_single_group_pow2_matches_cost_model():
    """Compiled element counts == the analytic model at Table-1 points."""
    for n in (16, 64, 512):
        par = max_parallel_neurons(n)
        params = bnn.init_params(bnn.BnnSpec((n, par)), jax.random.PRNGKey(n))
        prog = compile_bnn([np.asarray(w) for w in params])
        assert prog.num_elements == elements_for_neuron_group(n, par)
