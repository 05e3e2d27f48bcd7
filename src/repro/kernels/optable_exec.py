"""Pallas kernel: fused op-table executor for lowered pipeline programs.

The accelerator backend of ``dataplane.executor``.  The register file is laid
out transposed — ``(num_regs, batch)`` uint32, registers on the sublane axis,
packets on the lane axis — so each ALU row is a dynamic *row* gather
(``out_ref[pl.ds(slot, 1), :]``), the supported dynamic-index pattern, and
every scalar op applies across a full lane vector of packets at once (exactly
how a switch ALU spans the pipeline).

Grid: ``(batch_blocks, num_elements)`` with the element axis innermost; the
output block's index map ignores the element index, so the register block
stays resident in VMEM across the whole program for each batch block (the
same accumulator-residency pattern as ``bnn_matmul``).  Per element the
kernel makes two passes over the rows — compute into a scratch buffer, then
write back — preserving RMT's read-before-write semantics.  The eight
scalar tables travel as one ``(num_elements, 8, rows)`` int32 stack, one
element's slab per grid step in SMEM (``8 * rows * 4`` bytes, double
buffered); uint32 immediates are bitcast to int32 outside the kernel and
converted back per scalar inside it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.dataplane import lowering

_ALL_OPS = (
    lowering.XOR_IMM,
    lowering.SHR_AND_IMM,
    lowering.ADD,
    lowering.GE_IMM,
    lowering.SHL_IMM,
    lowering.POPCNT,
)


# Row order of the stacked scalar table (see ``optable_run``).
_OPC, _DST, _SRC0, _SRC1, _IMM0, _IMM1, _MASK, _FIRST = range(8)


def _kernel(tab_ref, regs_ref, out_ref, scratch_ref, *, rows: int, used: tuple):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        out_ref[...] = regs_ref[...]

    def compute_row(r, carry):
        # Shared opcode->expression table with the jnp backend; local import
        # keeps the kernels package from depending on dataplane at load time.
        from repro.dataplane.executor import alu_variants

        opc = tab_ref[_OPC, r]
        # Immediates travel as int32 (SMEM holds 32-bit words); the signed to
        # unsigned convert keeps every bit, and unlike a bitcast it is legal
        # on a scalar.
        i0 = tab_ref[_IMM0, r].astype(jnp.uint32)
        i1 = tab_ref[_IMM1, r].astype(jnp.uint32)
        m = tab_ref[_MASK, r].astype(jnp.uint32)

        r0 = out_ref[pl.ds(tab_ref[_SRC0, r], 1), :]
        r1 = out_ref[pl.ds(tab_ref[_SRC1, r], 1), :]

        variants = alu_variants(r0, r1, i0, i1, used)
        _, val = variants[0]
        for code, v in variants[1:]:
            val = jnp.where(opc == code, v, val)
        scratch_ref[pl.ds(r, 1), :] = val & m
        return carry

    jax.lax.fori_loop(0, rows, compute_row, 0)

    def write_row(r, carry):
        dst = tab_ref[_DST, r]
        val = scratch_ref[pl.ds(r, 1), :]
        cur = out_ref[pl.ds(dst, 1), :]
        # First writer of a slot overwrites; FOLD continuation rows deposit
        # additional (disjoint) bits additively.
        out_ref[pl.ds(dst, 1), :] = jnp.where(
            tab_ref[_FIRST, r] == 1, val, cur + val
        )
        return carry

    jax.lax.fori_loop(0, rows, write_row, 0)


def optable_run(
    regs: jax.Array,
    opcode: jax.Array,
    dst: jax.Array,
    src0: jax.Array,
    src1: jax.Array,
    imm0: jax.Array,
    imm1: jax.Array,
    mask: jax.Array,
    first_write: jax.Array,
    *,
    used: tuple | None = None,
    block_b: int = 256,
    interpret: bool = False,
    name: str | None = None,
) -> jax.Array:
    """Run the op-table over a transposed register file.

    ``regs``: (num_regs, batch) uint32 — parsed packets, one column each.
    Tables: (num_elements, max_rows) as produced by ``lowering``.  Returns
    the final (num_regs, batch) register file.  ``name`` names the kernel
    in the compiled program and the device trace; it defaults to the
    element range the tables cover, ``optable_e0_<num_elements>``.
    """
    num_regs, batch = regs.shape
    num_el, rows = opcode.shape
    if used is None:
        used = _ALL_OPS

    bb = min(block_b, batch)
    pad = (-batch) % bb
    if pad:
        regs = jnp.pad(regs, ((0, 0), (0, pad)))
    padded = batch + pad

    # One (num_elements, 8, rows) int32 stack: each grid step's block is one
    # element's (8, rows) slab, whose last two dims are the array's own —
    # the TPU tiling rule for a block that is not a multiple of (8, 128).
    as_i32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.int32)
    tables = jnp.stack(
        [
            opcode, dst, src0, src1,
            as_i32(imm0), as_i32(imm1), as_i32(mask), first_write,
        ],
        axis=1,
    ).astype(jnp.int32)
    table_spec = pl.BlockSpec(
        (None, 8, rows), lambda b, e: (e, 0, 0), memory_space=pltpu.SMEM
    )
    regs_spec = pl.BlockSpec((num_regs, bb), lambda b, e: (0, b))

    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, used=tuple(used)),
        grid=(padded // bb, num_el),
        in_specs=[table_spec, regs_spec],
        out_specs=regs_spec,
        out_shape=jax.ShapeDtypeStruct((num_regs, padded), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((rows, bb), jnp.uint32)],
        interpret=interpret,
        name=name or f"optable_e0_{num_el}",
    )(tables, regs)
    return out[:, :batch] if pad else out


def optable_run_segmented(
    regs: jax.Array,
    opcode: jax.Array,
    dst: jax.Array,
    src0: jax.Array,
    src1: jax.Array,
    imm0: jax.Array,
    imm1: jax.Array,
    mask: jax.Array,
    first_write: jax.Array,
    *,
    runs: tuple[tuple[int, int, tuple[int, ...]], ...],
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Run opcode-homogeneous element segments back to back.

    ``runs`` is ``LoweredProgram.opcode_runs()``: static ``(start, stop,
    used)`` element ranges.  Bit-identical to one :func:`optable_run` over
    the whole table with the union used-set — but each segment's kernel
    specializes ``alu_variants`` to that segment's opcodes, collapsing the
    per-row where-select chain to (usually) a single expression.  Each
    segment's kernel is named ``optable_e<start>_<stop>``.
    """
    for start, stop, used in runs:
        regs = optable_run(
            regs,
            opcode[start:stop], dst[start:stop],
            src0[start:stop], src1[start:stop],
            imm0[start:stop], imm1[start:stop],
            mask[start:stop], first_write[start:stop],
            used=used, block_b=block_b, interpret=interpret,
            name=f"optable_e{start}_{stop}",
        )
    return regs
