"""JAX executor for compiled RMT pipeline programs.

This is the "chip in software": it evaluates a :class:`PipelineProgram` on a
batch of packets using exactly the element semantics of RMT — every op of an
element reads the *incoming* PHV, all writes land simultaneously
(read-before-write), results are truncated to the destination field's width.

The interpreter is the correctness witness for the compiler: tests assert
bit-exact agreement with the mathematical BNN oracle (``core.bnn.forward``)
over random models and inputs.  A program compiled into recirculation
passes runs pass by pass: each pass keeps only its carried header, then
re-parses its input words from the packet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import Op, OpCode, PipelineProgram


def _width_mask(width: int) -> jnp.uint32:
    return jnp.uint32((1 << width) - 1 if width < 32 else 0xFFFFFFFF)


def _eval_op(op: Op, regs: jax.Array) -> jax.Array:
    """Evaluate one op against the element's incoming register file.

    ``regs``: (batch, num_fields) uint32.  Returns the (batch,) result.
    """
    code = op.opcode
    if code == OpCode.COPY:
        val = regs[:, op.srcs[0].fid]
    elif code == OpCode.XNOR_IMM:
        val = ~(regs[:, op.srcs[0].fid] ^ jnp.uint32(op.imm[0]))
    elif code == OpCode.AND_IMM:
        val = regs[:, op.srcs[0].fid] & jnp.uint32(op.imm[0])
    elif code == OpCode.SHR_AND_IMM:
        val = (regs[:, op.srcs[0].fid] >> jnp.uint32(op.imm[0])) & jnp.uint32(op.imm[1])
    elif code == OpCode.ADD:
        val = regs[:, op.srcs[0].fid] + regs[:, op.srcs[1].fid]
    elif code == OpCode.GE_IMM:
        val = (regs[:, op.srcs[0].fid] >= jnp.uint32(op.imm[0])).astype(jnp.uint32)
    elif code == OpCode.FOLD:
        val = jnp.zeros(regs.shape[0], jnp.uint32)
        for k, src in enumerate(op.srcs):
            val = val | (regs[:, src.fid] << jnp.uint32(k))
    elif code == OpCode.POPCNT:
        val = jax.lax.population_count(regs[:, op.srcs[0].fid])
    else:  # pragma: no cover
        raise ValueError(f"unknown opcode {code}")
    return val & _width_mask(op.dst.width)


def _run(prog: PipelineProgram, packets: jax.Array) -> jax.Array:
    batch = packets.shape[0]
    regs = jnp.zeros((batch, prog.num_fields), jnp.uint32)

    if prog.pass_plans is None:
        offsets = np.cumsum([0] + [f.width for f in prog.input_fields])
        passes = [(None, tuple(zip(prog.input_fields, offsets)), prog.elements)]
    else:
        # Recirculation: a pass starts from its carried header alone, and
        # its parser extracts its input words from the packet again.
        passes = [
            (pp.carried, pp.parsed, prog.elements[slice(*pp.element_range)])
            for pp in prog.pass_plans
        ]
    for carried, parsed, elements in passes:
        if carried is not None:
            keep = np.zeros(prog.num_fields, np.uint32)
            keep[[f.fid for f in carried]] = 1
            regs = regs * jnp.asarray(keep)[None, :]
        # Parser step: the input activation bits into the input fields.
        for f, off in parsed:
            bits = packets[:, off : off + f.width].astype(jnp.uint32)
            weights = jnp.uint32(1) << jnp.arange(f.width, dtype=jnp.uint32)
            regs = regs.at[:, f.fid].set(
                jnp.sum(bits * weights, axis=1, dtype=jnp.uint32)
            )

        # Execute elements: read-before-write within each element.
        for el in elements:
            if not el.ops:
                continue
            vals = [_eval_op(op, regs) for op in el.ops]
            idx = jnp.array([op.dst.fid for op in el.ops])
            regs = regs.at[:, idx].set(jnp.stack(vals, axis=1))

    # Deparse: output fields -> flat bit vector.
    outs = []
    for f in prog.output_fields:
        word = regs[:, f.fid]
        shifts = jnp.arange(f.width, dtype=jnp.uint32)
        outs.append(((word[:, None] >> shifts) & jnp.uint32(1)).astype(jnp.int32))
    return jnp.concatenate(outs, axis=1)


_RUNNER_CACHE: dict[str, object] = {}


def _compiled_runner(prog: PipelineProgram):
    # Keyed on the structural fingerprint, not id(prog): ids are reused after
    # GC, which could silently hand back a stale runner jitted for a *different*
    # program.  Fingerprints also dedupe identical recompilations.
    key = prog.fingerprint()
    fn = _RUNNER_CACHE.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(_run, prog))
        _RUNNER_CACHE[key] = fn
    return fn


def run_program(prog: PipelineProgram, packets: jax.Array) -> jax.Array:
    """Run a compiled program on a batch of packets.

    ``packets``: (batch, input_bits) {0,1} array — the parsed activation bits.
    Returns (batch, output_bits) {0,1} int32 — the network's Y vector.
    """
    packets = jnp.asarray(packets)
    if packets.ndim != 2 or packets.shape[1] != prog.input_bits:
        raise ValueError(
            f"expected (batch, {prog.input_bits}) packet bits, got {packets.shape}"
        )
    return _run(prog, packets)


def run_program_jit(prog: PipelineProgram, packets: jax.Array) -> jax.Array:
    """Jitted variant (program is a static compile-time constant)."""
    return _compiled_runner(prog)(jnp.asarray(packets))
