"""Dataplane subsystem: lowering, fused executor, traffic, fabric, telemetry.

The load-bearing contract is differential: the fused op-table executor must
be *bit-exact* with the legacy per-op interpreter (``run_program``) and the
mathematical oracle (``bnn.forward``) — across model shapes, chips, traffic
scenarios, backends, chunkings, and fabric partitionings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bnn, compile_bnn, interpreter
from repro.core.interpreter import run_program, run_program_jit
from repro.core.pipeline import RMT_NATIVE_POPCNT, ChipSpec
from repro.dataplane import (
    SwitchFabric,
    execute,
    execute_stream,
    lower_program,
    stage_telemetry,
    traffic,
)
from repro.dataplane.executor import _rechunk
from repro.dataplane.lowering import POPCNT, SHL_IMM

MODELS = [(8, 4), (32, 64, 32), (33, 17, 9), (96, 40, 12, 5)]


def _compiled(sizes, seed=0, chip=None):
    spec = bnn.BnnSpec(sizes)
    params = bnn.init_params(spec, jax.random.PRNGKey(seed))
    weights = [np.asarray(w) for w in params]
    prog = compile_bnn(weights, chip) if chip else compile_bnn(weights)
    return params, prog


def _packets(n, bits, seed=0):
    return np.random.default_rng(seed).integers(0, 2, (n, bits), dtype=np.int64)


# -- lowering ---------------------------------------------------------------

def test_lowering_tables_shape_and_row_counts():
    _, prog = _compiled((32, 64, 32))
    lp = lower_program(prog)
    e, r = lp.opcode.shape
    assert e == len(prog.elements)
    assert (lp.rows_per_element <= r).all()
    assert lp.num_ops == int(lp.rows_per_element.sum())
    # FOLD expands to one SHL micro-row per sign bit; everything else is 1:1.
    folds = sum(
        len(op.srcs) - 1
        for el in prog.elements
        for op in el.ops
        if op.opcode.name == "FOLD"
    )
    assert lp.num_ops == sum(len(el.ops) for el in prog.elements) + folds
    # Only FOLD continuation rows clear the first_write flag.
    n_rows = lp.rows_per_element
    real = np.concatenate([lp.first_write[i, : n_rows[i]] for i in range(e)])
    opc = np.concatenate([lp.opcode[i, : n_rows[i]] for i in range(e)])
    assert ((real == 1) | (opc == SHL_IMM)).all()


def test_lowering_compaction_shrinks_register_file():
    _, prog = _compiled((32, 64, 32))
    lp = lower_program(prog)
    lp_full = lower_program(prog, compact=False)
    assert lp.num_regs < lp_full.num_regs / 5
    assert lp.fingerprint() != lp_full.fingerprint()
    x = _packets(64, 32)
    np.testing.assert_array_equal(
        execute(lp, x, backend="jnp"), execute(lp_full, x, backend="jnp")
    )


def test_lowering_slice_out_of_range():
    _, prog = _compiled((8, 4))
    lp = lower_program(prog)
    with pytest.raises(ValueError):
        lp.slice_elements(0, lp.num_elements + 1)


# -- fused executor vs interpreter vs oracle --------------------------------

@pytest.mark.parametrize("sizes", MODELS)
def test_executor_bit_exact(sizes):
    params, prog = _compiled(sizes, seed=len(sizes))
    lp = lower_program(prog)
    x = _packets(193, sizes[0], seed=1)
    got = execute(lp, x, backend="jnp")
    np.testing.assert_array_equal(got, np.asarray(run_program(prog, x)))
    np.testing.assert_array_equal(
        got, np.asarray(bnn.forward(params, jnp.asarray(x)))
    )


@pytest.mark.parametrize("scenario", sorted(traffic.SCENARIOS))
def test_executor_bit_exact_per_scenario(scenario):
    params, prog = _compiled((32, 64, 32))
    lp = lower_program(prog)
    x = traffic.generate(scenario, 256, 32, seed=11)
    got = execute(lp, x, backend="jnp")
    np.testing.assert_array_equal(got, np.asarray(run_program(prog, x)))
    np.testing.assert_array_equal(
        got, np.asarray(bnn.forward(params, jnp.asarray(x)))
    )


def test_executor_native_popcnt_chip():
    params, prog = _compiled((64, 16, 8), chip=RMT_NATIVE_POPCNT)
    lp = lower_program(prog)
    assert POPCNT in lp.used_opcodes()
    x = _packets(100, 64, seed=2)
    got = execute(lp, x, backend="jnp")
    np.testing.assert_array_equal(got, np.asarray(run_program(prog, x)))
    np.testing.assert_array_equal(
        got, np.asarray(bnn.forward(params, jnp.asarray(x)))
    )


def test_executor_pallas_kernel_matches():
    _, prog = _compiled((16, 8, 4))
    lp = lower_program(prog)
    x = _packets(70, 16, seed=3)  # non-multiple of the batch block: pads
    want = execute(lp, x, backend="jnp")
    got = execute(lp, x, backend="pallas", interpret=True)
    np.testing.assert_array_equal(got, want)


def test_executor_chunked_equals_single_shot():
    _, prog = _compiled((16, 8))
    lp = lower_program(prog)
    x = _packets(333, 16, seed=4)
    np.testing.assert_array_equal(
        execute(lp, x, backend="jnp", chunk_size=128),
        execute(lp, x, backend="jnp"),
    )


def test_executor_rejects_bad_shapes():
    _, prog = _compiled((8, 4))
    lp = lower_program(prog)
    with pytest.raises(ValueError):
        execute(lp, _packets(10, 9))
    with pytest.raises(ValueError):
        execute(lp, _packets(10, 8), backend="nope")


# -- streaming --------------------------------------------------------------

def test_stream_equals_batch_and_counts_bits():
    _, prog = _compiled((16, 8, 4))
    lp = lower_program(prog)
    chunks = [_packets(97, 16, seed=i) for i in range(5)]
    allx = np.concatenate(chunks)
    sr = execute_stream(lp, iter(chunks), chunk_size=128, collect=True)
    want = execute(lp, allx, backend="jnp")
    np.testing.assert_array_equal(sr.outputs.astype(np.int32), want)
    np.testing.assert_array_equal(
        sr.bit_counts, want.sum(axis=0, dtype=np.int64)
    )
    assert sr.packets == allx.shape[0]
    assert sr.chunks == -(-allx.shape[0] // 128)
    assert sr.packets_per_second > 0


@pytest.mark.parametrize("collect", [True, False])
def test_empty_stream_collects_an_empty_array(collect):
    _, prog = _compiled((16, 8, 4))
    sr = execute_stream(lower_program(prog), iter([]), collect=collect)
    assert sr.packets == 0 and sr.chunks == 0
    if collect:
        assert sr.outputs.shape == (0, 4) and sr.outputs.dtype == np.uint8
    else:
        assert sr.outputs is None


def test_rechunk_reslices_exactly():
    chunks = [np.arange(n)[:, None] for n in (5, 1, 9, 2)]
    out = list(_rechunk(iter(chunks), 4))
    assert [c.shape[0] for c in out] == [4, 4, 4, 4, 1]
    np.testing.assert_array_equal(
        np.concatenate(out), np.concatenate(chunks)
    )


# -- traffic ----------------------------------------------------------------

@pytest.mark.parametrize("scenario", sorted(traffic.SCENARIOS))
def test_traffic_shape_values_determinism(scenario):
    a = traffic.generate(scenario, 200, 48, seed=7)
    b = traffic.generate(scenario, 200, 48, seed=7)
    c = traffic.generate(scenario, 200, 48, seed=8)
    assert a.shape == (200, 48) and a.dtype == np.int32
    assert set(np.unique(a)) <= {0, 1}
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)  # seeds matter
    assert 0.0 < a.mean() < 1.0      # neither all-zeros nor all-ones


def test_traffic_stream_chunks_and_determinism():
    got = list(traffic.stream("flow_tuple", 250, 32, chunk_size=64, seed=3))
    assert [c.shape[0] for c in got] == [64, 64, 64, 58]
    again = list(traffic.stream("flow_tuple", 250, 32, chunk_size=64, seed=3))
    np.testing.assert_array_equal(np.concatenate(got), np.concatenate(again))


def test_traffic_stream_keeps_world_across_chunks():
    # flow_tuple: every chunk draws from the one flow pool set up at stream
    # start — the whole stream shows at most the pool's 256 distinct headers.
    allx = np.concatenate(
        list(traffic.stream("flow_tuple", 2000, 32, chunk_size=128, seed=1))
    )
    assert len(np.unique(allx, axis=0)) <= 256

    # ddos_burst: burst phase follows *global* packet position, so the
    # second burst window (packets 1024..1279) still carries the signature
    # drawn at setup even though chunking restarted many times in between.
    allx = np.concatenate(
        list(traffic.stream("ddos_burst", 2048, 32, chunk_size=300, seed=2))
    )
    first, second = allx[:256], allx[1024:1280]
    signature = (first.mean(axis=0) > 0.5).astype(np.int32)
    agreement = (second == signature[None, :]).mean()
    assert agreement > 0.9  # jitter is 2% per bit

    # iot_telemetry: sensor walks continue across chunks — streamed traffic
    # stays low-entropy (far fewer distinct headers than packets).
    allx = np.concatenate(
        list(traffic.stream("iot_telemetry", 1500, 32, chunk_size=100, seed=3))
    )
    assert len(np.unique(allx, axis=0)) < 800


@pytest.mark.parametrize("scenario", sorted(traffic.SCENARIOS))
def test_traffic_stream_resumed_mid_scenario_matches_uninterrupted(scenario):
    # A chunked stream — any chunking, including ones that pause and resume
    # mid-trace — must replay exactly the uninterrupted sequence.  The
    # canonical-chunk scheme guarantees it; before it, emitters threading one
    # rng through differently-shaped draws broke this for 3 of 5 scenarios.
    n = 3000
    want = traffic.generate(scenario, n, 24, seed=5)
    for chunk_size in (1, 173, traffic.CANONICAL_CHUNK, n):
        got = np.concatenate(
            list(traffic.stream(scenario, n, 24, chunk_size=chunk_size, seed=5))
        )
        np.testing.assert_array_equal(got, want)
    # Resume: consume the first half from one stream object, the rest from a
    # fresh stream advanced past it — identical world, identical packets.
    first = traffic.generate(scenario, 1700, 24, seed=5)
    rest = traffic.generate(scenario, n, 24, seed=5)[1700:]
    np.testing.assert_array_equal(np.concatenate([first, rest]), want)


def test_mixed_tenant_stream_resumed_matches_uninterrupted():
    specs = [
        traffic.TenantTrafficSpec("ddos_burst", 16, 2.0),
        traffic.TenantTrafficSpec("uniform_random", 24, 1.0),
    ]
    n = 2500
    want_t, want_b = traffic.mixed_tenant_generate(specs, n, seed=9)
    for chunk_size in (47, 300, traffic.CANONICAL_CHUNK, n):
        chunks = list(
            traffic.mixed_tenant_stream(specs, n, chunk_size=chunk_size, seed=9)
        )
        np.testing.assert_array_equal(
            np.concatenate([t for t, _ in chunks]), want_t
        )
        np.testing.assert_array_equal(
            np.concatenate([b for _, b in chunks]), want_b
        )


def test_traffic_unknown_scenario():
    with pytest.raises(KeyError):
        traffic.get_scenario("does_not_exist")


# -- fabric -----------------------------------------------------------------

@pytest.mark.parametrize("mode", ["multi_hop", "recirculate"])
def test_fabric_partition_bit_exact(mode):
    params, prog = _compiled((32, 64, 32))
    tiny = ChipSpec(num_elements=7)  # forces a multi-switch chain
    fab = SwitchFabric.partition(prog, mode=mode, chip=tiny)
    assert fab.num_hops == -(-len(prog.elements) // 7)
    # Hops tile the element range exactly.
    ranges = [h.element_range for h in fab.hops]
    assert ranges[0][0] == 0 and ranges[-1][1] == len(prog.elements)
    assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))

    x = traffic.generate("ddos_burst", 211, 32, seed=5)
    res = fab.run(x, chunk_size=100)
    np.testing.assert_array_equal(res.outputs, np.asarray(run_program(prog, x)))
    np.testing.assert_array_equal(
        res.outputs, np.asarray(bnn.forward(params, jnp.asarray(x)))
    )


def test_fabric_single_hop_when_program_fits():
    _, prog = _compiled((8, 4))
    fab = SwitchFabric.partition(prog)
    assert fab.num_hops == 1


def test_fabric_pallas_backend_matches_jnp():
    _, prog = _compiled((16, 8, 4))
    fab = SwitchFabric.partition(prog, chip=ChipSpec(num_elements=9))
    x = _packets(40, 16, seed=6)
    want = fab.run(x, backend="jnp").outputs
    got = fab.run(x, backend="pallas", interpret=True).outputs
    np.testing.assert_array_equal(got, want)


def test_fabric_mode_validation():
    _, prog = _compiled((8, 4))
    with pytest.raises(ValueError):
        SwitchFabric.partition(prog, mode="teleport")


def test_fabric_throughput_accounting():
    _, prog = _compiled((32, 64, 32))
    tiny = ChipSpec(num_elements=8)
    multi = SwitchFabric.partition(prog, mode="multi_hop", chip=tiny)
    recirc = SwitchFabric.partition(prog, mode="recirculate", chip=tiny)
    # A switch chain pipelines at full line rate; recirculation divides by
    # the pass count — the paper's §2 trade.
    assert multi.analytic_report().packets_per_second == tiny.packets_per_second
    assert recirc.analytic_report().packets_per_second == pytest.approx(
        tiny.packets_per_second / recirc.num_hops
    )


# -- telemetry --------------------------------------------------------------

def test_stage_telemetry_liveness_and_budgets():
    _, prog = _compiled((32, 64, 32))
    stages = stage_telemetry(prog)
    assert len(stages) == len(prog.elements)
    assert stages[0].live_in_bits == prog.input_bits
    peak = max(s.occupancy_bits for s in stages)
    # Liveness-derived occupancy is bounded by the allocator's conservative
    # overlay accounting, which in turn respects the 512B PHV.
    assert 0 < peak <= prog.peak_phv_bits <= prog.chip.phv_bits
    for s in stages:
        assert 0 < s.alu_utilization <= 1.0
        assert s.ops > 0 and s.written_bits > 0


def test_fabric_telemetry_uses_fabric_chip():
    _, prog = _compiled((8, 4))
    other = ChipSpec(num_elements=4, phv_bits=8192, name="bigphv")
    tel = SwitchFabric.partition(prog, chip=other).telemetry()
    assert tel.chip_name == "bigphv"
    # PHV utilization is judged against the fabric's switches, not the
    # program's compile-time target.
    assert tel.phv_utilization == tel.peak_occupancy_bits / 8192


def test_fabric_telemetry_rollup_and_render():
    _, prog = _compiled((32, 64, 32))
    fab = SwitchFabric.partition(
        prog, mode="multi_hop", chip=ChipSpec(num_elements=8)
    )
    res = fab.run(_packets(64, 32), chunk_size=64)
    tel = fab.telemetry(res)
    assert len(tel.hops) == fab.num_hops
    assert tel.measured_pps == pytest.approx(res.packets_per_second)
    assert 0 < tel.phv_utilization <= 1.0
    text = tel.render()
    assert "multi_hop" in text and "measured" in text


# -- interpreter cache fix --------------------------------------------------

def test_runner_cache_keyed_structurally():
    params, prog_a = _compiled((8, 4), seed=1)
    _, prog_b = _compiled((8, 4), seed=1)   # identical structure, new object
    _, prog_c = _compiled((8, 4), seed=2)   # different weights
    assert prog_a.fingerprint() == prog_b.fingerprint()
    assert prog_a.fingerprint() != prog_c.fingerprint()
    # Memoized after first call: O(1) on the jitted dispatch hot path.
    assert prog_a.fingerprint() is prog_a.fingerprint()

    interpreter._RUNNER_CACHE.clear()
    x = _packets(32, 8)
    out_a = np.asarray(run_program_jit(prog_a, x))
    assert len(interpreter._RUNNER_CACHE) == 1
    # Structurally identical program reuses the jitted runner...
    np.testing.assert_array_equal(np.asarray(run_program_jit(prog_b, x)), out_a)
    assert len(interpreter._RUNNER_CACHE) == 1
    # ...while a different program gets (and computes with) its own.
    out_c = np.asarray(run_program_jit(prog_c, x))
    assert len(interpreter._RUNNER_CACHE) == 2
    np.testing.assert_array_equal(
        out_c, np.asarray(bnn.forward(bnn.init_params(bnn.BnnSpec((8, 4)), jax.random.PRNGKey(2)), jnp.asarray(x)))
    )
