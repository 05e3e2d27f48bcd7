"""Fused batched executor for lowered pipeline programs.

Runs a :class:`~repro.dataplane.lowering.LoweredProgram` over ``(chunk,
num_regs)`` uint32 register files — the whole program as *data*: a single
``jax.lax.scan`` over the element axis of the op-tables, with a branchless
ALU (the per-row opcode selects between vectorized variants) replacing the
legacy interpreter's per-op Python dispatch.  Bit-exact with
``core.interpreter.run_program`` by construction: same read-before-write
element semantics (gather everything, then scatter), same width masking.

Backends:

* ``"jnp"``   — the scan executor above; production path on CPU.
* ``"pallas"``— ``kernels.optable_exec`` kernel; production path on TPU,
  ``interpret=True`` elsewhere (tests).
* ``"packed"``— bit-packed PHV path: activation bits are packed into uint32
  lanes at parse time (one XLA scatter-add, the same on every device)
  and each neuron is one masked XNOR + ``population_count`` over 32 bits at
  a time instead of 32 op-table rows.  Requires a
  ``LoweredProgram.packed`` plan (compiler-built programs have one);
  operates on whole packets, so it has no ``run_hop`` form.
* ``"auto"``  — pallas on TPU, jnp otherwise (mirrors ``kernels.ops``).

The op-table backends execute in *opcode runs* (``LoweredProgram.
opcode_runs()``): consecutive elements sharing an opcode set are dispatched
with an ALU narrowed to exactly those opcodes, so the branchless
where-select chain collapses for the single-opcode elements the compiler
emits.  A program compiled into recirculation passes runs its opcode runs
inside one ``lax.scan`` over the passes' stacked tables (:func:`run_hop`):
each step re-parses the pass's input words from the packet and runs the
pass, over the one register file that carries the recirculation header.

Streaming (:func:`execute_stream`) re-chunks any packet iterator into
fixed-size blocks so millions of packets run at constant device memory and a
single compiled executable.  The stream path is instrumented through
``repro.obs`` (packets/chunk counters, chunk-latency histogram, and
``compile:``/``execute:`` spans) — all no-ops unless the global
observability switch is on (see ``docs/OBSERVABILITY.md``).  Each chunk of
:func:`execute_stream` and :func:`execute` passes through five phase spans,
``ingest``, ``h2d``, ``dispatch``, ``d2h`` and ``collect`` (:data:`PHASES`),
each carrying ``chunk=<k>`` (``dispatch`` also ``passes=<n>``, the
program's recirculation passes); while a JAX profiler session collects they
are written into its trace, so the device's idle time can be split among
them.

Routed parse/deparse (:func:`parse_packets_routed`,
:func:`deparse_regs_routed`) generalize the parser to per-packet program
selection — the entry point ``dataplane.multitenant`` uses to serve several
merged programs from one register file in a single pass.

Invariants:

* **Bit-exactness** — every backend, chunking, and streaming path returns
  exactly what ``core.interpreter.run_program`` (and hence the
  ``core.bnn.forward`` oracle) returns for the same program and packets.
* **One ALU table** — both backends evaluate opcodes through
  :func:`alu_variants`; a new dense opcode is added there (and in the
  Pallas kernel's mirror) or nowhere.
* **Register file == PHV** — the ``(num_regs, batch)`` uint32 file produced
  by :func:`parse_packets` is the packet state on the wire: fabric hops
  thread it through :func:`run_hop` unchanged in meaning, and
  :func:`deparse_regs` only reads, never mutates.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.dataplane import lowering
from repro.dataplane.lowering import LoweredProgram

DEFAULT_CHUNK = 1 << 15  # 32768 packets per device dispatch

# The phases of one served chunk, in order: pull and pad it on the host,
# copy it in, call the compiled dispatch, wait for and copy the result
# back, fold it into the run's outputs.
PHASES = ("ingest", "h2d", "dispatch", "d2h", "collect")

_BACKENDS = ("auto", "jnp", "pallas", "packed")
_BACKEND_ALIASES = {"fused": "jnp"}


def resolve_backend(backend="auto") -> str:
    """Normalize a backend choice to an executor backend string.

    Accepts the legacy strings, their aliases (``"fused"`` == ``"jnp"``),
    and :class:`repro.dataplane.plan.Backend` members — the typed
    :class:`~repro.dataplane.plan.ExecutionPlan` surface and the string
    keyword surface stay interchangeable.
    """
    backend = getattr(backend, "value", backend)  # plan.Backend -> str
    backend = _BACKEND_ALIASES.get(backend, backend)
    if backend == "interpreter":
        raise ValueError(
            "the interpreter backend is a reference path, not an executor: "
            "reach it through repro.dataplane.run(program, packets, "
            "plan=ExecutionPlan(backend=Backend.INTERPRETER))"
        )
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# ---------------------------------------------------------------------------
# Device-side tables (moved once per program, keyed on content fingerprint)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _DeviceTables:
    """Everything the hot loop needs, uploaded/derived once per program."""

    ops: tuple          # 7 (num_elements, max_rows) arrays for the scan
    first_write: jax.Array
    io: tuple           # in_slot, in_shift, out_slot, out_shift
    used: tuple         # static dense-opcode set (union over all elements)
    runs: tuple         # static (start, stop, used) opcode-homogeneous runs


_TABLE_CACHE: dict[str, _DeviceTables] = {}


def _device_tables(lp: LoweredProgram) -> _DeviceTables:
    key = lp.fingerprint()
    t = _TABLE_CACHE.get(key)
    if obs.enabled():
        # A table-cache miss is the executor-side proxy for "this program
        # will trace + jit-compile on its next dispatch" — the counter pair
        # the obs report turns into a cache hit rate.
        obs.registry().counter(
            "dataplane.table_cache_hits_total"
            if t is not None
            else "dataplane.table_cache_misses_total"
        ).inc()
    if t is None:
        # A program of passes: (passes, elements a pass, rows) for the scan.
        shape = (lp.passes, lp.elements_per_pass, lp.max_rows)

        def dev(a: np.ndarray) -> jax.Array:
            return jnp.asarray(a.reshape(shape) if lp.passes > 1 else a)

        t = _DeviceTables(
            ops=(
                dev(lp.opcode),
                dev(lp.dst),
                dev(lp.src0),
                dev(lp.src1),
                dev(lp.imm0),
                dev(lp.imm1),
                dev(lp.mask),
            ),
            first_write=dev(lp.first_write),
            io=(
                jnp.asarray(lp.in_slot_per_bit),
                jnp.asarray(lp.in_shift_per_bit),
                jnp.asarray(lp.out_slot_per_bit),
                jnp.asarray(lp.out_shift_per_bit),
            ),
            used=lp.used_opcodes(),
            runs=lp.opcode_runs(),
        )
        _TABLE_CACHE[key] = t
    return t


# ---------------------------------------------------------------------------
# Bit-packed PHV path (the "packed" backend)
# ---------------------------------------------------------------------------

_PACKED_CACHE: dict[str, object] = {}


def _pack_words(h: jax.Array, in_word, in_shift, n_words: int) -> jax.Array:
    """Deposit (batch, bits) {0,1} uint32 into (batch, n_words) uint32 PHV
    lanes: bit ``k`` lands in word ``in_word[k]`` at ``in_shift[k]``.  Bits
    of one word are disjoint, so the scatter-add is an OR."""
    words = jnp.zeros((h.shape[0], n_words), jnp.uint32)
    return words.at[:, in_word].add(h << in_shift)


def _packed_fn(lp: LoweredProgram):
    """Compile ``lp.packed`` into a jitted (batch, input_bits) {0,1} ->
    (batch, output_bits) int32 function, cached per program fingerprint.

    Per layer: pack the incoming bits into ``n_words`` uint32 PHV lanes
    (:func:`_pack_words`), then for every neuron count agreements with one
    masked XNOR + ``population_count`` per 32-bit word and compare against
    the SIGN threshold.  Bit-exact with the op-table scan — the fuzz suite
    (tests/test_differential_fuzz.py) holds the two together.
    """
    key = lp.fingerprint()
    fn = _PACKED_CACHE.get(key)
    if fn is not None:
        return fn
    pp = lp.packed
    if pp is None:
        raise ValueError(
            "program has no bit-packed plan (LoweredProgram.packed is None "
            "for hand-assembled tables and element slices); use the "
            "op-table backends"
        )
    layers = tuple(
        (
            jnp.asarray(pl_.weights),
            jnp.asarray(pl_.thresholds),
            jnp.asarray(pl_.mask),
            jnp.asarray(pl_.in_word),
            jnp.asarray(pl_.in_shift),
            pl_.n_words,
        )
        for pl_ in pp.layers
    )

    @jax.jit
    def run(packets: jax.Array) -> jax.Array:
        h = packets.astype(jnp.uint32)  # (batch, bits in neuron order)
        for w, thr, mask, in_word, in_shift, n_words in layers:
            words = _pack_words(h, in_word, in_shift, n_words)
            agree = jax.lax.population_count(
                ~(words[:, None, :] ^ w[None, :, :]) & mask[None, :, :]
            )
            count = jnp.sum(agree, axis=-1, dtype=jnp.uint32)
            h = (count >= thr[None, :]).astype(jnp.uint32)
        return h.astype(jnp.int32)

    _PACKED_CACHE[key] = run
    return run


_PACKED_SCAN_CACHE: dict[str, object] = {}


def _packed_scan_fn(lp: LoweredProgram):
    """Scan-over-layers variant of the packed executor.

    Layers are padded to common shapes and stacked
    (``lowering.stack_packed_layers``), then the whole network runs as ONE
    ``lax.scan`` over the layer axis: the layer body compiles once however
    deep the network is — the recirculation analogue for the packed
    backend (each scan step is one hop's worth of packed compute carried in
    the packet's bit vector).  Bit-exact with :func:`_packed_fn` because
    padding is inert by construction (zero masks, unreachable thresholds);
    the differential fuzz suite holds the two together.
    """
    key = lp.fingerprint()
    fn = _PACKED_SCAN_CACHE.get(key)
    if fn is not None:
        return fn
    if lp.packed is None:
        raise ValueError(
            "program has no bit-packed plan (LoweredProgram.packed is None "
            "for hand-assembled tables and element slices); use the "
            "op-table backends"
        )
    sp = lowering.stack_packed_layers(lp.packed)
    stacked = (
        jnp.asarray(sp.weights),
        jnp.asarray(sp.thresholds),
        jnp.asarray(sp.mask),
        jnp.asarray(sp.in_word),
        jnp.asarray(sp.in_shift),
    )
    max_bits, max_words = sp.max_bits, sp.max_words
    out_bits = sp.output_bits

    @jax.jit
    def run(packets: jax.Array) -> jax.Array:
        h = packets.astype(jnp.uint32)
        pad = max_bits - h.shape[1]
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad)))

        def layer(h, tbl):
            w, thr, mask, in_word, in_shift = tbl
            # Pad input bits carry 0 (outputs past a layer's true width
            # never fire), so their word-0 scatter adds nothing.
            words = _pack_words(h, in_word, in_shift, max_words)
            agree = jax.lax.population_count(
                ~(words[:, None, :] ^ w[None, :, :]) & mask[None, :, :]
            )
            count = jnp.sum(agree, axis=-1, dtype=jnp.uint32)
            nxt = (count >= thr[None, :]).astype(jnp.uint32)
            if max_bits > nxt.shape[1]:
                nxt = jnp.pad(nxt, ((0, 0), (0, max_bits - nxt.shape[1])))
            return nxt, None

        h, _ = jax.lax.scan(layer, h, stacked)
        return h[:, :out_bits].astype(jnp.int32)

    _PACKED_SCAN_CACHE[key] = run
    return run


# ---------------------------------------------------------------------------
# Parser / ALU scan / deparser (jnp backend)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_regs",))
def parse_packets(packets: jax.Array, in_slot, in_shift, *, num_regs: int):
    """(batch, input_bits) {0,1} -> (num_regs, batch) uint32 register files.

    The register file is transposed — registers on the leading axis — so the
    executor's per-row gathers and scatters are contiguous row copies instead
    of strided column accesses (and the Pallas kernel's sublane-dense file
    is its row-major reshape, ``optable_exec.to_dense``).
    """
    pkt = packets.astype(jnp.uint32).T  # (input_bits, batch)
    regs = jnp.zeros((num_regs, packets.shape[0]), jnp.uint32)
    return regs.at[in_slot, :].add(pkt << in_shift[:, None])


@jax.jit
def deparse_regs(regs: jax.Array, out_slot, out_shift) -> jax.Array:
    """(num_regs, batch) -> (batch, output_bits) {0,1} int32."""
    words = jnp.take(regs, out_slot, axis=0)  # (output_bits, batch)
    return ((words >> out_shift[:, None]) & jnp.uint32(1)).T.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_regs",))
def parse_packets_routed(
    packets: jax.Array,
    program_ids: jax.Array,
    slot_table: jax.Array,
    shift_table: jax.Array,
    valid_table: jax.Array,
    *,
    num_regs: int,
):
    """Per-packet-program parser for a shared register file.

    ``packets``: (batch, max_bits) {0,1}; ``program_ids``: (batch,) int32
    selecting each packet's row of the ``(num_programs, max_bits)`` parser
    tables.  Bits whose ``valid_table`` entry is 0 (width padding for
    narrower programs) land harmlessly in the null slot with value 0.  This
    is how a multi-tenant merge parses a mixed stream into disjoint
    register windows in one dispatch (``dataplane.multitenant``).
    """
    batch = packets.shape[0]
    pkt = packets.astype(jnp.uint32)                    # (batch, max_bits)
    slots = jnp.take(slot_table, program_ids, axis=0)   # (batch, max_bits)
    shifts = jnp.take(shift_table, program_ids, axis=0)
    valid = jnp.take(valid_table, program_ids, axis=0)
    vals = (pkt & valid) << shifts
    regs = jnp.zeros((num_regs, batch), jnp.uint32)
    cols = jnp.arange(batch, dtype=jnp.int32)[:, None]
    return regs.at[slots, cols].add(vals)


@jax.jit
def deparse_regs_routed(
    regs: jax.Array,
    program_ids: jax.Array,
    out_slot_table: jax.Array,
    out_shift_table: jax.Array,
) -> jax.Array:
    """(num_regs, batch) -> (batch, max_out_bits) {0,1} int32, reading each
    packet's bits through its own program's deparser table.  Width-padding
    entries point at the null register and deparse as 0."""
    batch = regs.shape[1]
    slots = jnp.take(out_slot_table, program_ids, axis=0)   # (batch, bits)
    shifts = jnp.take(out_shift_table, program_ids, axis=0)
    cols = jnp.arange(batch, dtype=jnp.int32)[:, None]
    words = regs[slots, cols]                               # (batch, bits)
    return ((words >> shifts) & jnp.uint32(1)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("total_bits",))
def route_bits_in(
    packets: jax.Array,
    program_ids: jax.Array,
    bit_table: jax.Array,
    valid_table: jax.Array,
    *,
    total_bits: int,
) -> jax.Array:
    """Dense-bit analogue of :func:`parse_packets_routed` for the packed
    backend: scatter each packet's bits to its program's window of a
    ``(batch, total_bits)`` merged input-bit vector.

    ``bit_table``/``valid_table`` are ``(num_programs, max_bits)``; invalid
    (width-padding) entries carry index 0 and valid 0, so they add nothing.
    """
    pkt = packets.astype(jnp.uint32)
    idx = jnp.take(bit_table, program_ids, axis=0)      # (batch, max_bits)
    valid = jnp.take(valid_table, program_ids, axis=0)
    out = jnp.zeros((packets.shape[0], total_bits), jnp.uint32)
    cols = jnp.arange(packets.shape[0], dtype=jnp.int32)[:, None]
    return out.at[cols, idx].add(pkt & valid)


@jax.jit
def route_bits_out(
    bits: jax.Array,
    program_ids: jax.Array,
    bit_table: jax.Array,
) -> jax.Array:
    """Gather each packet's output bits back out of a merged dense bit
    vector through its program's ``(num_programs, max_out_bits)`` routing
    table.  Width-padding entries gather bit 0; callers slice them off per
    tenant just as with :func:`deparse_regs_routed`."""
    idx = jnp.take(bit_table, program_ids, axis=0)      # (batch, max_out)
    return jnp.take_along_axis(bits, idx, axis=1).astype(jnp.int32)


def alu_variants(r0, r1, i0, i1, used: tuple) -> list:
    """The dense-opcode ALU: ``[(code, value), ...]`` for the opcodes in
    ``used``.  Shared by the jnp scan executor and the Pallas kernel so both
    backends compute from one opcode->expression table (the bit-exactness
    contract between them hangs on these staying identical)."""
    table = (
        (lowering.XOR_IMM, lambda: r0 ^ i0),
        (lowering.SHR_AND_IMM, lambda: (r0 >> i0) & i1),
        (lowering.ADD, lambda: r0 + r1),
        (lowering.GE_IMM, lambda: (r0 >= i0).astype(jnp.uint32)),
        (lowering.SHL_IMM, lambda: r0 << i0),
        (lowering.POPCNT, lambda: jax.lax.population_count(r0)),
    )
    return [(code, expr()) for code, expr in table if code in used]


def _element_scan(regs: jax.Array, tables: tuple, used: tuple) -> jax.Array:
    """The fused inner loop body: scan the op-table over the register file.

    Traceable (not jitted here) so both :func:`run_elements` and the
    stacked-hop scan (:func:`run_hops_scanned`) compile the SAME element
    step — bit-exactness between the unrolled and scanned fabric paths is
    by shared construction, then fuzz-proven.
    """

    def step(regs, tbl):
        opc, dst, s0, s1, i0, i1, m = tbl
        r0 = jnp.take(regs, s0, axis=0)  # (rows, batch), contiguous rows
        r1 = jnp.take(regs, s1, axis=0)

        variants = alu_variants(r0, r1, i0[:, None], i1[:, None], used)
        _, val = variants[0]
        for code, v in variants[1:]:
            val = jnp.where((opc == code)[:, None], v, val)
        val = val & m[:, None]

        # Element write-back: zero every written slot, then scatter-add.  One
        # writer per slot except FOLD micro-rows, whose contributions carry
        # disjoint bits (add == OR).  Pad rows add 0 to the null register.
        regs = regs.at[dst, :].set(jnp.uint32(0)).at[dst, :].add(val)
        return regs, None

    regs, _ = jax.lax.scan(step, regs, tables)
    return regs


@functools.partial(jax.jit, static_argnames=("used",))
def run_elements(regs: jax.Array, tables: tuple, *, used: tuple):
    """Scan the op-table over the register file (the fused inner loop).

    ``regs``: (num_regs, batch).  ``used`` is the static tuple of dense
    opcodes present, so the branchless ALU only materializes variants the
    program can select.
    """
    return _element_scan(regs, tables, used)


@functools.partial(jax.jit, static_argnames=("used",))
def _run_hops_stacked(regs: jax.Array, tables: tuple, *, used: tuple):
    """Nested scan: hops on the outside, elements inside — the whole fabric
    chain as ONE compiled dispatch over ``(H, E, rows)`` stacked tables."""

    def hop(regs, tbl):
        return _element_scan(regs, tbl, used), None

    regs, _ = jax.lax.scan(hop, regs, tables)
    return regs


_STACKED_CACHE: dict[tuple, object] = {}


def run_hops_scanned(
    stacked,
    regs: jax.Array,
    *,
    backend: str = "jnp",
    interpret: bool | None = None,
) -> jax.Array:
    """Run a :class:`~repro.dataplane.lowering.StackedHops` chain over parsed
    register files as a single ``lax.scan`` over the hop axis.

    Bit-exact with calling :func:`run_hop` per hop slice: the scan body IS
    the shared element step (op-table backends) or the Pallas kernel with
    the hop tables as scan-carried operands.  The union opcode set trades
    the per-run ALU narrowing of the unrolled path for one compiled body —
    results are identical either way.
    """
    backend = resolve_backend(backend)
    if backend == "packed":
        raise ValueError(
            "the packed backend scans layers, not register-file hops "
            "(see execute(..., scan_hops=True))"
        )
    if backend == "pallas" and interpret is None:
        interpret = jax.default_backend() != "tpu"
    key = (stacked.fingerprint, backend, bool(interpret))
    entry = _STACKED_CACHE.get(key)
    if entry is None:
        tables = tuple(
            jnp.asarray(getattr(stacked, name))
            for name in (
                "opcode", "dst", "src0", "src1", "imm0", "imm1", "mask",
            )
        )
        first_write = jnp.asarray(stacked.first_write)
        used = stacked.used
        if backend == "pallas":
            from repro.kernels import optable_exec as ox

            interp = bool(interpret)

            @jax.jit
            def scanned(regs, tabs, fw):
                def hop(dense, tbl):
                    t, f = tbl
                    return (
                        ox.optable_run_dense(
                            dense, *t, f, used=used, interpret=interp
                        ),
                        None,
                    )

                # One relayout each way around the whole hop scan.
                dense = ox.to_dense(regs, fw.shape[-1])
                dense, _ = jax.lax.scan(hop, dense, (tabs, fw))
                return ox.from_dense(dense, regs.shape[1])

            entry = (lambda r: scanned(r, tables, first_write))
        else:
            entry = (
                lambda r: _run_hops_stacked(r, tables, used=used)
            )
        _STACKED_CACHE[key] = entry
    return entry(regs)


def run_hop(
    lowered: LoweredProgram,
    regs: jax.Array,
    *,
    backend: str = "jnp",
    interpret: bool | None = None,
) -> jax.Array:
    """Run one program (or fabric-hop slice) over parsed register files.

    The (num_regs, batch) register file in/out *is* the PHV on the wire —
    ``fabric.SwitchFabric`` chains hops by threading it through here.  A
    program of recirculation passes runs as one ``lax.scan`` over its
    passes, each step the pass's opcode runs (its re-parse element first).
    """
    backend = resolve_backend(backend)
    if backend == "packed":
        raise ValueError(
            "the packed backend consumes whole packets (execute / "
            "execute_stream), not register-file hops"
        )
    t = _device_tables(lowered)
    if backend == "pallas":
        from repro.kernels import optable_exec as ox

        if interpret is None:
            interpret = jax.default_backend() != "tpu"

        def step(dense, ops, first_write):
            return ox.optable_run_segmented_dense(
                dense, *ops, first_write, runs=t.runs, interpret=interpret
            )

    else:

        def step(regs, ops, first_write):
            for start, stop, used in t.runs:
                regs = run_elements(
                    regs, tuple(a[start:stop] for a in ops), used=used
                )
            return regs

    def all_passes(regs):
        if lowered.passes == 1:
            return step(regs, t.ops, t.first_write)

        def one_pass(regs, tables):
            return step(regs, *tables), None

        regs, _ = jax.lax.scan(one_pass, regs, (t.ops, t.first_write))
        return regs

    if backend != "pallas":
        return all_passes(regs)
    # The kernel's dense layout, made once around every pass.
    dense = all_passes(ox.to_dense(regs, lowered.max_rows))
    return ox.from_dense(dense, regs.shape[1])


# ---------------------------------------------------------------------------
# Fused routed dispatch (merged multi-tenant programs)
# ---------------------------------------------------------------------------

_ROUTED_CACHE: dict[tuple, object] = {}


def _routing_key(*tables: np.ndarray) -> int:
    """Content hash of per-tenant routing tables.

    Merged-program fingerprints are insertion-order canonical (so the table
    caches dedupe permuted tenant sets), but the tenant-id-indexed routing
    tables are NOT order-invariant — two schedulers admitting the same
    programs in different orders share op-tables yet route differently.  The
    routed caches therefore key on fingerprint *plus* routing content.
    """
    return hash(tuple(np.asarray(t).tobytes() for t in tables))


def _invert_bit_routing(bit_table, valid_table, total_bits: int):
    """Forward scatter tables -> inverse gather tables.

    :func:`route_bits_in`'s per-packet scatter (``out.at[cols, idx].add``)
    serializes on CPU/GPU — XLA lowers dynamic-index scatter-add to a
    sequential loop.  The routing is a bijection from each program's valid
    packet columns onto its disjoint window, so it inverts exactly: for
    every dense position, which packet column feeds it (``src``) and
    whether it is fed at all (``ok``).  The fused dispatch then needs only
    ``take_along_axis`` gathers, which vectorize.
    """
    bt = np.asarray(bit_table)
    vt = np.asarray(valid_table).astype(bool)
    src = np.zeros((bt.shape[0], total_bits), np.int32)
    ok = np.zeros((bt.shape[0], total_bits), np.uint32)
    for p in range(bt.shape[0]):
        cols = np.nonzero(vt[p])[0]
        src[p, bt[p, cols]] = cols
        ok[p, bt[p, cols]] = 1
    return src, ok


def _invert_parse_routing(slot_table, shift_table, valid_table):
    """Register-file analogue of :func:`_invert_bit_routing`.

    Each program maps its valid packet columns onto distinct
    ``(slot, shift)`` pairs; only a handful of slots (the input registers)
    ever receive parser bits.  Returns ``(slots, col, ok)`` where ``slots``
    is that receiving set and ``col``/``ok`` are ``(programs, len(slots),
    32)`` gather tables: word ``s`` of a packet's register file is the
    OR over ``k`` of ``packet[col[p, s, k]] << k``.
    """
    st = np.asarray(slot_table)
    sh = np.asarray(shift_table)
    vt = np.asarray(valid_table).astype(bool)
    num_programs = st.shape[0]
    slots = np.unique(st[vt]) if vt.any() else np.zeros(1, np.int64)
    index_of = {int(s): i for i, s in enumerate(slots)}
    col = np.zeros((num_programs, len(slots), 32), np.int32)
    ok = np.zeros((num_programs, len(slots), 32), np.uint32)
    for p in range(num_programs):
        for c in np.nonzero(vt[p])[0]:
            col[p, index_of[int(st[p, c])], int(sh[p, c])] = c
            ok[p, index_of[int(st[p, c])], int(sh[p, c])] = 1
    return slots.astype(np.int32), col, ok


def routed_fn(
    lp: LoweredProgram,
    in_slot: np.ndarray,
    in_shift: np.ndarray,
    in_valid: np.ndarray,
    out_slot: np.ndarray,
    out_shift: np.ndarray,
    *,
    backend: str = "jnp",
    interpret: bool | None = None,
):
    """One-jit merged dispatch: routed parse -> opcode-run execution ->
    routed deparse, compiled as a single ``(packets, program_ids) ->
    output bits`` executable.

    Fusing the three phases removes the per-chunk multi-dispatch overhead of
    calling :func:`parse_packets_routed` / :func:`run_hop` /
    :func:`deparse_regs_routed` separately (one device round-trip per opcode
    run) — the register file never leaves the compiled computation.  Cached
    per (program fingerprint, backend, interpret, routing content).
    """
    backend = resolve_backend(backend)
    if backend == "packed":
        raise ValueError(
            "the packed backend routes dense bits, not register files; use "
            "routed_packed_fn"
        )
    if backend == "pallas" and interpret is None:
        interpret = jax.default_backend() != "tpu"
    key = (
        lp.fingerprint(), backend, bool(interpret),
        _routing_key(in_slot, in_shift, in_valid, out_slot, out_shift),
    )
    fn = _ROUTED_CACHE.get(key)
    if fn is not None:
        return fn
    t = _device_tables(lp)
    reg_slots, parse_col, parse_ok = _invert_parse_routing(
        in_slot, in_shift, in_valid
    )
    n_slots = len(reg_slots)
    d_reg_slots = jnp.asarray(reg_slots)
    d_col = jnp.asarray(parse_col.reshape(parse_col.shape[0], -1))
    d_ok = jnp.asarray(parse_ok.reshape(parse_ok.shape[0], -1))
    d_out_slot = jnp.asarray(out_slot)
    d_out_shift = jnp.asarray(out_shift)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    num_regs = lp.num_regs

    def parse(packets: jax.Array, program_ids: jax.Array) -> jax.Array:
        batch = packets.shape[0]
        pkt = packets.astype(jnp.uint32)
        cols = jnp.take(d_col, program_ids, axis=0)   # (batch, slots*32)
        ok = jnp.take(d_ok, program_ids, axis=0)
        bits = jnp.take_along_axis(pkt, cols, axis=1) & ok
        words = jnp.sum(
            bits.reshape(batch, n_slots, 32) << shifts[None, None, :],
            axis=2,
            dtype=jnp.uint32,
        )
        regs = jnp.zeros((num_regs, batch), jnp.uint32)
        return regs.at[d_reg_slots].set(words.T)

    if backend == "pallas":
        from repro.kernels.optable_exec import optable_run_segmented

        runs = t.runs
        interp = bool(interpret)

        @jax.jit
        def fn(packets: jax.Array, program_ids: jax.Array) -> jax.Array:
            regs = parse(packets, program_ids)
            # Relaid out to the kernel's dense file once, around every run.
            regs = optable_run_segmented(
                regs, *t.ops, t.first_write, runs=runs, interpret=interp
            )
            return deparse_regs_routed(
                regs, program_ids, d_out_slot, d_out_shift
            )

    else:
        @jax.jit
        def fn(packets: jax.Array, program_ids: jax.Array) -> jax.Array:
            regs = parse(packets, program_ids)
            for start, stop, used in t.runs:
                regs = _element_scan(
                    regs, tuple(a[start:stop] for a in t.ops), used
                )
            return deparse_regs_routed(
                regs, program_ids, d_out_slot, d_out_shift
            )

    _ROUTED_CACHE[key] = fn
    return fn


def routed_packed_fn(
    lp: LoweredProgram,
    packed_in_bit: np.ndarray,
    packed_out_bit: np.ndarray,
    in_valid: np.ndarray,
):
    """Packed-backend twin of :func:`routed_fn`: route dense bits into the
    merged packed program's input window, run the block-diagonal XNOR/popcnt
    chain, and gather each packet's bits back out — one jit end to end."""
    pp = lp.packed
    if pp is None:
        raise ValueError(
            "merged program has no packed plan; every tenant must carry one "
            "(compiler-built programs do)"
        )
    key = (
        lp.fingerprint(), "packed",
        _routing_key(packed_in_bit, in_valid, packed_out_bit),
    )
    fn = _ROUTED_CACHE.get(key)
    if fn is not None:
        return fn
    inner = _packed_fn(lp)
    src_tbl, ok_tbl = _invert_bit_routing(
        packed_in_bit, in_valid, pp.input_bits
    )
    d_src = jnp.asarray(src_tbl)
    d_ok = jnp.asarray(ok_tbl)
    d_out = jnp.asarray(packed_out_bit)

    @jax.jit
    def fn(packets: jax.Array, program_ids: jax.Array) -> jax.Array:
        pkt = packets.astype(jnp.uint32)
        src = jnp.take(d_src, program_ids, axis=0)
        ok = jnp.take(d_ok, program_ids, axis=0)
        dense = jnp.take_along_axis(pkt, src, axis=1) & ok
        return route_bits_out(inner(dense), program_ids, d_out)

    _ROUTED_CACHE[key] = fn
    return fn


_ROUTED_STACK_CACHE: dict[tuple, object] = {}


def routed_packed_stacked_fn(lowereds: tuple):
    """Widest-tenant packed dispatch for an interleaved merge.

    The block-diagonal merged packed program (``routed_packed_fn``) makes
    every packet XNOR against every tenant's words — per-chunk work scales
    with the *sum* of tenant widths.  Here each tenant's packed layers are
    instead stacked along a leading tenant axis, padded to the widest
    block per stage (pad neurons carry unreachable thresholds, so they
    emit 0), and each packet gathers its own tenant's weight block by
    ``program_id`` — per-chunk work scales with the *widest/deepest*
    tenant per stage.  Inputs and outputs stay tenant-local (bit ``i`` of
    tenant ``t`` lives at position ``i`` for every tenant), so no bit
    routing is needed at either end.

    Returns ``None`` when any tenant lacks a packed plan or uses a
    non-trivial word layout (hand-assembled programs) — callers fall back
    to the block-diagonal merged plan.
    """
    key = tuple(lp.fingerprint() for lp in lowereds)
    fn = _ROUTED_STACK_CACHE.get(key)
    if fn is not None:
        return fn
    packs = [lp.packed for lp in lowereds]
    if any(pp is None for pp in packs):
        return None
    depth = max(len(pp.layers) for pp in packs)
    columns = []
    for pp in packs:
        ls = list(pp.layers)
        while len(ls) < depth:
            ls.append(lowering.PackedLayer.identity(ls[-1].n_out))
        for pl in ls:
            bit = np.arange(pl.n_in)
            if not (
                np.array_equal(pl.in_word, bit // 32)
                and np.array_equal(pl.in_shift, bit % 32)
            ):
                return None
        columns.append(ls)
    stacked = []
    for layer_idx in range(depth):
        pls = [c[layer_idx] for c in columns]
        max_n = max(pl.n_out for pl in pls)
        max_w = max(pl.n_words for pl in pls)
        w = np.zeros((len(pls), max_n, max_w), np.uint32)
        m = np.zeros((len(pls), max_n, max_w), np.uint32)
        # Pad neurons can never fire: agreement tops out at 32 * words.
        thr = np.full((len(pls), max_n), 0xFFFFFFFF, np.uint32)
        for t, pl in enumerate(pls):
            w[t, : pl.n_out, : pl.n_words] = pl.weights
            m[t, : pl.n_out, : pl.n_words] = pl.mask
            thr[t, : pl.n_out] = pl.thresholds
        stacked.append(
            (jnp.asarray(w), jnp.asarray(thr), jnp.asarray(m), max_w)
        )
    stacked = tuple(stacked)
    shifts = jnp.arange(32, dtype=jnp.uint32)

    @jax.jit
    def fn(packets: jax.Array, program_ids: jax.Array) -> jax.Array:
        h = packets.astype(jnp.uint32)   # (batch, bits), tenant-local
        for w_tbl, thr_tbl, m_tbl, n_words in stacked:
            need = n_words * 32
            if h.shape[1] < need:
                h = jnp.pad(h, ((0, 0), (0, need - h.shape[1])))
            else:
                h = h[:, :need]
            words = jnp.sum(
                h.reshape(h.shape[0], n_words, 32) << shifts[None, None, :],
                axis=2,
                dtype=jnp.uint32,
            )
            w = jnp.take(w_tbl, program_ids, axis=0)  # (batch, maxN, maxW)
            m = jnp.take(m_tbl, program_ids, axis=0)
            thr = jnp.take(thr_tbl, program_ids, axis=0)
            agree = jax.lax.population_count(
                ~(words[:, None, :] ^ w) & m
            )
            count = jnp.sum(agree, axis=-1, dtype=jnp.uint32)
            h = (count >= thr).astype(jnp.uint32)
        return h.astype(jnp.int32)

    _ROUTED_STACK_CACHE[key] = fn
    return fn


def _chunk_body(lp: LoweredProgram, backend: str, interpret, scan_hops: bool):
    """Traceable (chunk, bits) {0,1} -> (chunk, out_bits) int32 for one
    stream: parse -> :func:`run_hop` -> deparse, or the packed function.
    :func:`_run_chunk` jits it; ``fleet.fleet_fn`` vmaps it over streams."""
    if backend == "packed":
        return _packed_scan_fn(lp) if scan_hops else _packed_fn(lp)
    t = _device_tables(lp)
    in_slot, in_shift, out_slot, out_shift = t.io

    def run(block: jax.Array) -> jax.Array:
        regs = parse_packets(block, in_slot, in_shift, num_regs=lp.num_regs)
        regs = run_hop(lp, regs, backend=backend, interpret=interpret)
        return deparse_regs(regs, out_slot, out_shift)

    return run


_CHUNK_CACHE: dict[tuple, object] = {}


def _chunk_fn(
    lp: LoweredProgram, backend: str, interpret, scan_hops: bool = False
):
    """:func:`_chunk_body` under one ``jax.jit`` (module
    ``jit_stream_chunk``), cached per (program fingerprint, backend,
    interpret, scan_hops): a chunk shape lowers once, on its first call."""
    backend = resolve_backend(backend)
    key = (
        lp.fingerprint(),
        backend,
        None if interpret is None else bool(interpret),
        bool(scan_hops),
    )
    fn = _CHUNK_CACHE.get(key)
    if obs.enabled():
        obs.registry().counter(
            "dataplane.chunk_fn_cache_hits_total"
            if fn is not None
            else "dataplane.chunk_fn_cache_misses_total"
        ).inc()
    if fn is None:
        body = _chunk_body(lp, backend, interpret, scan_hops)

        def stream_chunk(block: jax.Array) -> jax.Array:
            return body(block)

        fn = _CHUNK_CACHE[key] = jax.jit(stream_chunk)
    return fn


def _kernel_pad(lp: LoweredProgram, backend: str, batch: int) -> int:
    """Packet columns the op-table kernel pads a ``batch``-packet dispatch
    by to fill its tiles (0 off the ``pallas`` backend)."""
    if backend != "pallas":
        return 0
    from repro.kernels.optable_exec import pad_packets

    return pad_packets(batch, lp.num_regs, lp.max_rows)


def _run_chunk(
    lp: LoweredProgram,
    packets: jax.Array,
    backend: str,
    interpret: bool | None,
    scan_hops: bool = False,
) -> jax.Array:
    """One chunk through the program's one compiled dispatch."""
    return _chunk_fn(lp, backend, interpret, scan_hops)(packets)


# ---------------------------------------------------------------------------
# Public batch / streaming API
# ---------------------------------------------------------------------------

def execute(
    lowered: LoweredProgram,
    packets,
    *,
    backend: str = "auto",
    chunk_size: int | None = None,
    interpret: bool | None = None,
    scan_hops: bool = False,
) -> np.ndarray:
    """Run ``packets`` (N, input_bits) {0,1} through the program.

    Returns (N, output_bits) int32, bit-exact with
    ``interpreter.run_program``.  Batches larger than ``chunk_size`` stream
    in fixed-size chunks (constant device memory, one compiled executable).
    ``scan_hops=True`` runs the packed backend's scan-over-layers plan
    (``_packed_scan_fn``) instead of the unrolled layer loop; op-table
    backends ignore it (their hop structure lives in ``fabric``).
    """
    packets = np.asarray(packets)
    if packets.ndim != 2 or packets.shape[1] != lowered.input_bits:
        raise ValueError(
            f"expected (batch, {lowered.input_bits}) packet bits, "
            f"got {packets.shape}"
        )
    backend = resolve_backend(backend)
    n = packets.shape[0]
    chunk = chunk_size or DEFAULT_CHUNK
    if n <= chunk:  # a batch that fits runs unpadded, as one chunk
        size, starts, out = n, (0,), None
    else:
        size, starts = chunk, range(0, n, chunk)
        out = np.empty((n, lowered.output_bits), np.int32)
    for k, start in enumerate(starts):
        with obs.span("ingest", cat="phase", chunk=k):
            block = packets[start : start + size]
            pad = size - block.shape[0]
            if pad:
                block = np.pad(block, ((0, pad), (0, 0)))
        with obs.span("h2d", cat="phase", chunk=k):
            dev = jnp.asarray(block)
        with obs.span("dispatch", cat="phase", chunk=k, passes=lowered.passes):
            res = _run_chunk(lowered, dev, backend, interpret, scan_hops)
        if obs.enabled():
            obs.registry().counter("dataplane.kernel_pad_packets_total").inc(
                _kernel_pad(lowered, backend, size)
            )
        with obs.span("d2h", cat="phase", chunk=k):
            res = np.asarray(res)
        with obs.span("collect", cat="phase", chunk=k):
            if out is None:  # the only chunk: its rows are the result
                return res[:n]
            out[start : start + size] = res[: size - pad]
    return out


@dataclasses.dataclass
class StreamResult:
    """Outcome of a streamed run — the simulator's line-rate measurement."""

    packets: int
    chunks: int
    seconds: float
    bit_counts: np.ndarray            # (output_bits,) int64: ones per Y bit
    outputs: np.ndarray | None = None  # (packets, output_bits) uint8 if collected
    warmup_seconds: float = 0.0        # first-chunk warm call (incl. jit compile)

    @property
    def packets_per_second(self) -> float:
        return self.packets / self.seconds if self.seconds > 0 else float("inf")


def _rechunk(chunks: Iterable[np.ndarray], chunk_size: int) -> Iterator[np.ndarray]:
    """Re-slice an arbitrary chunk stream into exactly-``chunk_size`` blocks
    (last block may be short)."""
    buf: list[np.ndarray] = []
    have = 0
    for c in chunks:
        c = np.asarray(c)
        while c.shape[0]:
            take = min(chunk_size - have, c.shape[0])
            buf.append(c[:take])
            have += take
            c = c[take:]
            if have == chunk_size:
                yield np.concatenate(buf, axis=0) if len(buf) > 1 else buf[0]
                buf, have = [], 0
    if have:
        yield np.concatenate(buf, axis=0) if len(buf) > 1 else buf[0]


def execute_stream(
    lowered: LoweredProgram,
    chunks: Iterable[np.ndarray],
    *,
    backend: str = "auto",
    chunk_size: int = DEFAULT_CHUNK,
    collect: bool = False,
    interpret: bool | None = None,
    scan_hops: bool = False,
) -> StreamResult:
    """Stream a packet-chunk iterator through the executor.

    With ``collect=False`` (default) only aggregate statistics are kept —
    memory stays constant no matter how many packets flow.  ``seconds``
    reads each chunk from the start of its ``ingest`` phase (pulling it
    from ``chunks`` and padding it) to the end of its ``collect`` phase
    (folding its verdicts into the result): the host-to-device copy, the
    dispatch and the wait for the device with the copy back all fall
    inside.  The first chunk is dispatched twice; its first, warm call
    (trace and compile) is left out of ``seconds`` and reported as
    ``warmup_seconds``.
    """
    backend = resolve_backend(backend)
    bit_counts = np.zeros(lowered.output_bits, np.int64)
    collected: list[np.ndarray] = []
    total = 0
    n_chunks = 0
    seconds = 0.0
    warmup = 0.0
    blocks = _rechunk(chunks, chunk_size)
    with obs.span(
        "stream:execute_stream", cat="stream",
        backend=backend, chunk_size=chunk_size,
    ):
        while True:
            k = n_chunks
            t0 = time.perf_counter()
            with obs.span("ingest", cat="phase", chunk=k):
                block = next(blocks, None)
                if block is not None:
                    n = block.shape[0]
                    pad = chunk_size - n
                    if pad:
                        block = np.pad(block, ((0, pad), (0, 0)))
            if block is None:
                break
            with obs.span("h2d", cat="phase", chunk=k):
                dev = jnp.asarray(block)
            warm = 0.0
            if k == 0:  # warm the compile cache outside the clock
                with obs.span(
                    "compile:stream_chunk", cat="compile",
                    backend=backend, packets=chunk_size,
                ), obs.span(
                    "dispatch", cat="phase", chunk=k, passes=lowered.passes,
                    warm=True,
                ):
                    w0 = time.perf_counter()
                    _run_chunk(
                        lowered, dev, backend, interpret, scan_hops
                    ).block_until_ready()
                    warm = warmup = time.perf_counter() - w0
            with obs.span("execute:stream_chunk", cat="execute", packets=n):
                with obs.span(
                    "dispatch", cat="phase", chunk=k, passes=lowered.passes
                ):
                    res = _run_chunk(lowered, dev, backend, interpret, scan_hops)
                with obs.span("d2h", cat="phase", chunk=k):
                    res = np.asarray(res)
            with obs.span("collect", cat="phase", chunk=k):
                res = res[:n]
                bit_counts += res.sum(axis=0, dtype=np.int64)
                if collect:
                    collected.append(res.astype(np.uint8))
            dt = time.perf_counter() - t0 - warm
            seconds += dt
            total += n
            n_chunks += 1
            if obs.enabled():
                m = obs.registry()
                m.counter("dataplane.packets_total").inc(n)
                m.counter("dataplane.chunks_total").inc()
                m.counter("dataplane.recirc_passes_total").inc(
                    n * lowered.passes
                )
                m.counter("dataplane.kernel_pad_packets_total").inc(
                    _kernel_pad(lowered, backend, chunk_size)
                )
                m.histogram("dataplane.chunk_seconds").observe(dt)
    if obs.enabled() and seconds > 0:
        obs.registry().gauge("dataplane.stream_pps").set(total / seconds)
    outputs = None
    if collect:  # an empty stream collects an empty array, not None
        outputs = (
            np.concatenate(collected, axis=0)
            if collected
            else np.zeros((0, lowered.output_bits), np.uint8)
        )
    return StreamResult(
        packets=total,
        chunks=n_chunks,
        seconds=seconds,
        bit_counts=bit_counts,
        outputs=outputs,
        warmup_seconds=warmup,
    )
