"""Pallas kernel: fused op-table executor for lowered pipeline programs.

The accelerator backend of ``dataplane.executor``.  The kernel works on a
*sublane-dense* register file, ``(num_regs, 8, lanes)`` uint32: the
row-major reshape of the executor's ``(num_regs, batch)`` file
(:func:`to_dense`), so packet ``p`` sits at sublane ``p // lanes``, lane
``p % lanes``.  The register slot is the leading, untiled axis: an ALU row
reads and writes one slot as ``ref[slot]``, a full ``(8, lanes)`` tile at a
dynamic leading index (the access Mosaic handles best), and every scalar op
applies across all eight sublanes of each vreg, eight times the packets of
a one-row ``(1, batch)`` layout for the same vector-op count.

Grid: ``(batch_blocks, num_elements)`` with the element axis innermost; the
output block's index map ignores the element index, so the register block
stays resident in VMEM across the whole program for each batch block (the
same accumulator-residency pattern as ``bnn_matmul``).  The block's lane
width comes from the shapes alone (:func:`_block_lanes`): the whole array
when it fits, else the widest ``128 * k`` lanes whose in, out
(double-buffered) and scratch blocks fit a VMEM budget.  Per element the
kernel makes two passes over the rows — compute into a scratch buffer, then
write back — preserving RMT's read-before-write semantics.  The eight
scalar tables travel as one ``(num_elements, 8, rows)`` int32 stack, one
element's slab per grid step in SMEM (``8 * rows * 4`` bytes, double
buffered); uint32 immediates are bitcast to int32 outside the kernel and
converted back per scalar inside it.

The dispatch paths relayout once around everything they run
(:func:`to_dense` / :func:`from_dense`, outside any pass or hop scan) and
call the ``*_dense`` entries; :func:`optable_run` and
:func:`optable_run_segmented` take and return ``(num_regs, batch)`` for any
other caller.
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

# Row order of the stacked scalar table (see ``optable_run_dense``).
_OPC, _DST, _SRC0, _SRC1, _IMM0, _IMM1, _MASK, _FIRST = range(8)

_SUBLANES = 8
_LANES = 128
# Three quarters of v5e's 16 MiB default scoped VMEM, for a block's in and
# out (each double-buffered) and its scratch; the rest is Mosaic's.
_VMEM_BUDGET = 12 << 20


def _lane_cap(num_regs: int, rows: int) -> int:
    """The widest multiple of 128 lanes whose blocks fit the VMEM budget."""
    per_lane = (4 * num_regs + rows) * _SUBLANES * 4
    return max(_LANES, _VMEM_BUDGET // per_lane // _LANES * _LANES)


def dense_lanes(batch: int, num_regs: int, rows: int) -> int:
    """Lanes of the dense register file for ``batch`` packets: the batch
    over eight sublanes, rounded up to whole 128-lane tiles only where one
    block over the whole file would not fit the VMEM budget."""
    lanes = -(-batch // _SUBLANES)
    if lanes > _lane_cap(num_regs, rows):
        lanes = -(-lanes // _LANES) * _LANES
    return lanes


def pad_packets(batch: int, num_regs: int, rows: int) -> int:
    """Packet columns the kernel pads ``batch`` by to fill its tiles."""
    return dense_lanes(batch, num_regs, rows) * _SUBLANES - batch


def _block_lanes(lanes: int, num_regs: int, rows: int) -> int:
    """One block over all ``lanes`` if it fits, else the widest ``128 * k``
    that divides them and fits (``lanes`` is then a multiple of 128)."""
    cap = _lane_cap(num_regs, rows)
    if lanes <= cap:
        return lanes
    tiles = lanes // _LANES
    k = max(d for d in range(1, cap // _LANES + 1) if tiles % d == 0)
    return k * _LANES


def to_dense(regs: jax.Array, rows: int) -> jax.Array:
    """``(num_regs, batch)`` -> the kernel's ``(num_regs, 8, lanes)`` file,
    padding the batch with zero packets as :func:`pad_packets` says."""
    num_regs, batch = regs.shape
    pad = pad_packets(batch, num_regs, rows)
    if pad:
        regs = jnp.pad(regs, ((0, 0), (0, pad)))
    return regs.reshape(num_regs, _SUBLANES, -1)


def from_dense(dense: jax.Array, batch: int) -> jax.Array:
    """Inverse of :func:`to_dense`: the first ``batch`` packets as
    ``(num_regs, batch)``."""
    return dense.reshape(dense.shape[0], -1)[:, :batch]


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

        r0 = out_ref[tab_ref[_SRC0, r]]
        r1 = out_ref[tab_ref[_SRC1, r]]

        variants = alu_variants(r0, r1, i0, i1, used)
        _, val = variants[0]
        for code, v in variants[1:]:
            val = jnp.where(opc == code, v, val)
        scratch_ref[r] = val & m
        return carry

    jax.lax.fori_loop(0, rows, compute_row, 0)

    def write_row(r, carry):
        dst = tab_ref[_DST, r]
        val = scratch_ref[r]
        cur = out_ref[dst]
        # First writer of a slot overwrites; FOLD continuation rows deposit
        # additional (disjoint) bits additively.
        out_ref[dst] = jnp.where(tab_ref[_FIRST, r] == 1, val, cur + val)
        return carry

    jax.lax.fori_loop(0, rows, write_row, 0)


def optable_run_dense(
    dense: jax.Array,
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
    interpret: bool = False,
    name: str | None = None,
) -> jax.Array:
    """Run the op-table over a dense register file.

    ``dense``: (num_regs, 8, lanes) uint32, as :func:`to_dense` makes it.
    Tables: (num_elements, max_rows) as produced by ``lowering``.  Returns
    the final dense register file.  ``name`` names the kernel in the
    compiled program and the device trace; it defaults to the element
    range the tables cover, ``optable_e0_<num_elements>``.
    """
    num_regs, _, lanes = dense.shape
    num_el, rows = opcode.shape
    if used is None:
        used = _ALL_OPS
    bl = _block_lanes(lanes, num_regs, rows)
    if lanes % bl:
        raise ValueError(
            f"{lanes} lanes do not tile into blocks; make the file with to_dense"
        )

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
    regs_spec = pl.BlockSpec(
        (num_regs, _SUBLANES, bl), lambda b, e: (0, 0, b)
    )

    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, used=tuple(used)),
        grid=(lanes // bl, num_el),
        in_specs=[table_spec, regs_spec],
        out_specs=regs_spec,
        out_shape=jax.ShapeDtypeStruct(dense.shape, jnp.uint32),
        scratch_shapes=[pltpu.VMEM((rows, _SUBLANES, bl), jnp.uint32)],
        interpret=interpret,
        name=name or f"optable_e0_{num_el}",
    )(tables, dense)


def optable_run_segmented_dense(
    dense: jax.Array,
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
    interpret: bool = False,
) -> jax.Array:
    """Run opcode-homogeneous element segments back to back on a dense file.

    ``runs`` is ``LoweredProgram.opcode_runs()``: static ``(start, stop,
    used)`` element ranges.  Bit-identical to one :func:`optable_run_dense`
    over the whole table with the union used-set — but each segment's
    kernel specializes ``alu_variants`` to that segment's opcodes,
    collapsing the per-row where-select chain to (usually) a single
    expression.  Each segment's kernel is named ``optable_e<start>_<stop>``.
    """
    for start, stop, used in runs:
        dense = optable_run_dense(
            dense,
            opcode[start:stop], dst[start:stop],
            src0[start:stop], src1[start:stop],
            imm0[start:stop], imm1[start:stop],
            mask[start:stop], first_write[start:stop],
            used=used, interpret=interpret,
            name=f"optable_e{start}_{stop}",
        )
    return dense


def optable_run(regs: jax.Array, *tables: jax.Array, **kw) -> jax.Array:
    """:func:`optable_run_dense` on a ``(num_regs, batch)`` register file
    (parsed packets, one column each), relaid out on the way in and out."""
    rows = tables[0].shape[-1]
    dense = optable_run_dense(to_dense(regs, rows), *tables, **kw)
    return from_dense(dense, regs.shape[1])


def optable_run_segmented(
    regs: jax.Array, *tables: jax.Array, **kw
) -> jax.Array:
    """:func:`optable_run_segmented_dense` on a ``(num_regs, batch)``
    register file, relaid out once on the way in and once out."""
    rows = tables[0].shape[-1]
    dense = optable_run_segmented_dense(to_dense(regs, rows), *tables, **kw)
    return from_dense(dense, regs.shape[1])
