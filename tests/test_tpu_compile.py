"""Compile the served path's dispatches for a described TPU v5e.

No chip is attached here: ``jax.experimental.topologies`` describes a
``v5e:2x2`` host, and each case lowers and compiles one dispatch with the
TPU compiler, for one of its devices or, for the sharded fleet, all four.  That catches what interpret-mode tests
cannot — block shapes the TPU tiling rules refuse, scalar ops Mosaic cannot
lower — at the paper's widths and the executor's default chunk.  Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker running this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec,
    SingleDeviceSharding,
)

from repro import sharding
from repro.configs import n2net_paper
from repro.core import bnn, compile_bnn
from repro.core.pipeline import ChipSpec
from repro.dataplane import executor, fleet, lower_program
from repro.dataplane.multitenant import SwitchScheduler
from repro.kernels.optable_exec import optable_run_segmented

CHUNK = executor.DEFAULT_CHUNK
PAPER = ("HEADLINE", "FIVE_TUPLE", "SINGLE_NEURON_2048")  # 32/128/2048 bits


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lowered(name: str):
    spec = getattr(n2net_paper, name)
    params = bnn.init_params(spec, jax.random.PRNGKey(0))
    return lower_program(compile_bnn([np.asarray(w) for w in params]))


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("name", PAPER)
def test_optable_kernel_compiles_for_v5e(one_chip, name):
    lp = _lowered(name)
    tables = [
        lp.opcode, lp.dst, lp.src0, lp.src1,
        lp.imm0, lp.imm1, lp.mask, lp.first_write,
    ]
    specs = [
        jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=one_chip)
        for t in tables
    ]
    regs = jax.ShapeDtypeStruct(
        (lp.num_regs, CHUNK), jnp.uint32, sharding=one_chip
    )
    runs = lp.opcode_runs()
    text = _compiled_text(
        lambda r, *t: optable_run_segmented(r, *t, runs=runs), regs, *specs
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("scan", [False, True], ids=["layers", "scan"])
@pytest.mark.parametrize("name", PAPER)
def test_packed_dispatch_compiles_for_v5e(one_chip, name, scan):
    lp = _lowered(name)
    fn = executor._packed_scan_fn(lp) if scan else executor._packed_fn(lp)
    packets = jax.ShapeDtypeStruct(
        (CHUNK, lp.input_bits), jnp.int32, sharding=one_chip
    )
    _compiled_text(fn, packets)


@pytest.mark.parametrize("backend", ["pallas", "packed"])
def test_stream_chunk_compiles_for_v5e(one_chip, backend):
    """The stream path's one cached dispatch: parse, every opcode run's
    kernel and deparse in the single module ``jit_stream_chunk``."""
    lp = _lowered("HEADLINE")
    packets = jax.ShapeDtypeStruct(
        (CHUNK, lp.input_bits), jnp.int32, sharding=one_chip
    )
    fn = executor._chunk_fn(lp, backend, False)
    text = fn.lower(packets).compile().as_text()
    assert text.startswith("HloModule jit_stream_chunk")
    kernels = len(lp.opcode_runs()) if backend == "pallas" else 0
    assert text.count('custom_call_target="tpu_custom_call"') == kernels


def test_routed_merged_dispatch_compiles_for_v5e(one_chip):
    shapes = ((16, 8, 4), (32, 16), (8, 12, 6))
    progs = [
        compile_bnn(
            [np.asarray(w) for w in bnn.init_params(
                bnn.BnnSpec(s), jax.random.PRNGKey(i)
            )]
        )
        for i, s in enumerate(shapes)
    ]
    sched = SwitchScheduler(
        ChipSpec(
            num_elements=sum(p.num_elements for p in progs) + 1,
            phv_bits=sum(p.peak_phv_bits for p in progs),
            name="shared",
        ),
        mode="merged",
    )
    for p in progs:
        sched.admit(p)
    mp = sched.merged("interleave")
    fn = executor.routed_fn(
        mp.lowered,
        mp.in_slot, mp.in_shift, mp.in_valid,
        mp.out_slot, mp.out_shift,
        backend="pallas", interpret=False,
    )
    chunk = 4096
    packets = jax.ShapeDtypeStruct(
        (chunk, mp.in_slot.shape[1]), jnp.int32, sharding=one_chip
    )
    ids = jax.ShapeDtypeStruct((chunk,), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, packets, ids)


@pytest.mark.parametrize("backend", ["pallas", "packed"])
def test_sharded_fleet_compiles_for_four_v5e_chips(topo, backend):
    """``ExecutionPlan(devices=4)``'s dispatch: the vmapped chunk function
    under ``shard_map`` over a 4-chip ``fleet`` mesh, streams split 4 ways."""
    lp = _lowered("HEADLINE")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("fleet",))
    fn = sharding.shard_streams(
        jax.vmap(executor._chunk_body(lp, backend, False, False)), mesh
    )
    blocks = jax.ShapeDtypeStruct(
        (16, fleet.DEFAULT_STREAM_CHUNK, lp.input_bits), jnp.int32,
        sharding=NamedSharding(mesh, PartitionSpec("fleet")),
    )
    compiled = jax.jit(fn).lower(blocks).compile()
    assert len(compiled.output_shardings.device_set) == 4
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_recirculation_chunk_compiles_for_v5e(one_chip):
    """Paper Table 1's 2048-bit activations, 2048-64-32 in recirculation
    passes: the pass scan with its opcode runs' kernels inside the one
    module ``jit_stream_chunk``, at the benchmark cell's chunk."""
    rng = np.random.default_rng(16)
    weights = [rng.integers(0, 2, (64, 2048)), rng.integers(0, 2, (32, 64))]
    lp = lower_program(compile_bnn(weights))
    assert lp.passes > 1
    packets = jax.ShapeDtypeStruct((8192, 2048), jnp.int32, sharding=one_chip)
    text = executor._chunk_fn(lp, "pallas", False).lower(packets).compile().as_text()
    assert text.startswith("HloModule jit_stream_chunk")
    assert text.count('custom_call_target="tpu_custom_call"') == len(lp.opcode_runs())
