"""Fleet execution: N independent packet streams through ONE dispatch.

The paper's premise is a chip forwarding billions of packets per second;
what starves the simulator is not compute but orchestration — one Python
dispatch per stream per chunk.  This module batches a *fleet* of independent
simulated switches (each with its own packet stream) through a single
compiled executor:

* the per-stream chunk function (parse -> op-table scan -> deparse, or the
  bit-packed XNOR/popcount path) is ``jax.vmap``-ed over a leading stream
  axis, so a ``(streams, chunk, bits)`` block is one device dispatch however
  many switches it carries;
* with ``ExecutionPlan.devices`` set, the stream axis is sharded over a 1-D
  ``fleet`` device mesh via ``shard_map`` (``repro.sharding.fleet_mesh`` /
  ``shard_streams``) — no collectives, streams never communicate;
* per-stream chunk iterators of *different* lengths are zipped into fleet
  blocks by :func:`fleet_blocks`, zero-padding exhausted or short streams
  (every executor backend maps packet rows independently, so pad rows cannot
  perturb real ones — the same argument that makes chunk padding safe in
  ``executor.execute``).

Because every backend is packet-row-independent, the vmapped fleet is
bit-exact with running each stream alone through ``executor.execute`` — the
fuzz suite (``tests/test_fleet.py``) holds fleet, single-stream, and the
interpreter oracle together, including mid-stream resume.

Entry points: :func:`execute_fleet` (stats + optional per-stream outputs,
the same warmup-outside-the-clock timing discipline and the same phase
spans per block as ``execute_stream``) and
:func:`fleet_fn` (the raw compiled callable, used by ``serving.engine``'s
async pipeline).  Reach both through ``repro.dataplane.run(program, streams,
plan=ExecutionPlan(fleet=N, ...))``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro import sharding as _sharding
from repro.dataplane import executor as _executor
from repro.dataplane.lowering import LoweredProgram, lower_program
from repro.dataplane.plan import ExecutionPlan

# Per-stream packets per dispatch.  Smaller than executor.DEFAULT_CHUNK on
# purpose: the fleet dimension restores the device-saturating batch size
# (64 streams x 4096 = 256k packet rows per dispatch).
DEFAULT_STREAM_CHUNK = 1 << 12

_FLEET_CACHE: dict[tuple, object] = {}


def fleet_fn(
    lowered: LoweredProgram,
    *,
    backend: str = "auto",
    interpret: bool | None = None,
    scan_hops: bool = False,
    devices: int | None = None,
):
    """The compiled fleet executable: ``(streams, chunk, bits)`` {0,1} ->
    ``(streams, chunk, out_bits)`` int32, cached per (program fingerprint,
    backend, interpret, scan_hops, devices).

    ``devices=None`` is pure vmap on the default device; an integer shards
    the stream axis over that many local devices (which must divide the
    stream count at call time).
    """
    backend = _executor.resolve_backend(backend)
    key = (
        lowered.fingerprint(),
        backend,
        None if interpret is None else bool(interpret),
        bool(scan_hops),
        devices,
    )
    fn = _FLEET_CACHE.get(key)
    if fn is not None:
        return fn
    batched = jax.vmap(
        _executor._chunk_body(lowered, backend, interpret, scan_hops)
    )
    if devices is not None:
        batched = _sharding.shard_streams(
            batched, _sharding.fleet_mesh(devices)
        )
    fn = jax.jit(batched)
    _FLEET_CACHE[key] = fn
    return fn


def fleet_blocks(
    streams: Sequence, chunk: int, input_bits: int
) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """Zip per-stream chunk iterators into ``(S, chunk, input_bits)`` int32
    blocks plus ``(S,)`` valid-row counts, until every stream is exhausted.

    Streams may have different lengths and chunkings: each is re-sliced to
    exactly ``chunk`` rows per block, and a stream that runs dry (or yields
    a short final chunk) is zero-padded — pad rows are dead weight the
    caller slices off via the valid counts.
    """
    its = [_executor._rechunk(s, chunk) for s in streams]
    n = len(its)
    done = [False] * n
    while True:
        blocks = np.zeros((n, chunk, input_bits), np.int32)
        valid = np.zeros(n, np.int64)
        got = False
        for i, it in enumerate(its):
            if done[i]:
                continue
            try:
                b = next(it)
            except StopIteration:
                done[i] = True
                continue
            blocks[i, : b.shape[0]] = b
            valid[i] = b.shape[0]
            got = True
        if not got:
            return
        yield blocks, valid


@dataclasses.dataclass
class FleetRunResult:
    """Outcome of a fleet run — the simulator's *aggregate* line rate."""

    streams: int
    packets: int                      # total across the fleet
    chunks: int                       # fleet blocks dispatched
    seconds: float
    per_stream_packets: np.ndarray    # (streams,) int64
    bit_counts: np.ndarray            # (output_bits,) int64, fleet-wide
    outputs: list | None = None       # per-stream (n_i, out_bits) uint8
    warmup_seconds: float = 0.0       # first-block warm call (incl. compile)
    backend: str = "auto"
    devices: int = 1

    @property
    def packets_per_second(self) -> float:
        return self.packets / self.seconds if self.seconds > 0 else float("inf")

    @property
    def pps_per_stream(self) -> float:
        return self.packets_per_second / self.streams if self.streams else 0.0


def _normalize_streams(streams, fleet: int | None) -> list:
    """Accept a (S, n, bits) array, a (n, bits) array (replicated to
    ``fleet`` switches), or a sequence of per-stream arrays/chunk-iterables;
    return a list of per-stream chunk iterables."""
    if hasattr(streams, "ndim"):
        arr = np.asarray(streams)
        if arr.ndim == 3:
            streams = [arr[i] for i in range(arr.shape[0])]
        elif arr.ndim == 2:
            if fleet is None:
                raise ValueError(
                    "a single (batch, bits) array needs plan.fleet to say "
                    "how many switches replicate it"
                )
            streams = [arr] * fleet
        else:
            raise ValueError(f"expected 2-D or 3-D packets, got {arr.shape}")
    streams = list(streams)
    if fleet is not None and len(streams) != fleet:
        if len(streams) == 1:
            streams = streams * fleet
        else:
            raise ValueError(
                f"plan.fleet={fleet} but {len(streams)} streams were given"
            )
    return [
        [np.asarray(s)] if hasattr(s, "ndim") else s for s in streams
    ]


def execute_fleet(
    lowered,
    streams,
    *,
    plan: ExecutionPlan | None = None,
) -> FleetRunResult:
    """Run N independent streams through one vmapped (optionally
    shard_map-ed) executor; bit-exact per stream with
    ``executor.execute(lowered, stream_i)``.

    Timing follows ``execute_stream``'s discipline: ``seconds`` reads each
    block from the start of its ``ingest`` phase (``fleet_blocks`` filling
    it stream by stream) to the end of its ``collect`` phase (the
    per-stream verdicts folded in), with the host-to-device copy, the
    dispatch and the copy back inside; the first block's warm call (trace
    and compile) is left out and reported as ``warmup_seconds``.
    """
    if not isinstance(lowered, LoweredProgram):
        lowered = lower_program(lowered)
    plan = plan or ExecutionPlan()
    backend = _executor.resolve_backend(plan.backend_str)
    chunk = plan.chunk_size or DEFAULT_STREAM_CHUNK
    its = _normalize_streams(streams, plan.fleet)
    n_streams = len(its)
    if plan.devices is not None and n_streams % plan.devices != 0:
        raise ValueError(
            f"fleet of {n_streams} streams does not shard evenly over "
            f"{plan.devices} devices"
        )
    fn = fleet_fn(
        lowered,
        backend=backend,
        interpret=plan.interpret,
        scan_hops=bool(plan.scan_hops),
        devices=plan.devices,
    )

    bit_counts = np.zeros(lowered.output_bits, np.int64)
    per_stream = np.zeros(n_streams, np.int64)
    collected = [[] for _ in range(n_streams)] if plan.collect else None
    seconds = 0.0
    warmup = 0.0
    n_blocks = 0
    blocks_in = fleet_blocks(its, chunk, lowered.input_bits)
    with obs.span(
        "stream:fleet_run", cat="stream",
        streams=n_streams, backend=backend, chunk_size=chunk,
        devices=plan.devices or 1,
    ):
        while True:
            k = n_blocks
            t0 = time.perf_counter()
            with obs.span("ingest", cat="phase", chunk=k):
                item = next(blocks_in, None)
            if item is None:
                break
            blocks, valid = item
            with obs.span("h2d", cat="phase", chunk=k):
                dev = jnp.asarray(blocks)
            warm = 0.0
            if k == 0:  # warm the compile cache outside the clock
                with obs.span(
                    "compile:fleet_chunk", cat="compile",
                    streams=n_streams, packets=n_streams * chunk,
                ), obs.span(
                    "dispatch", cat="phase", chunk=k, passes=lowered.passes,
                    warm=True,
                ):
                    w0 = time.perf_counter()
                    fn(dev).block_until_ready()
                    warm = warmup = time.perf_counter() - w0
            served = int(valid.sum())
            with obs.span(
                "execute:fleet_chunk", cat="execute", packets=served
            ):
                with obs.span(
                    "dispatch", cat="phase", chunk=k, passes=lowered.passes
                ):
                    res = fn(dev)
                with obs.span("d2h", cat="phase", chunk=k):
                    res = np.asarray(res)
            with obs.span("collect", cat="phase", chunk=k):
                for i in range(n_streams):
                    v = int(valid[i])
                    if not v:
                        continue
                    rows = res[i, :v]
                    bit_counts += rows.sum(axis=0, dtype=np.int64)
                    per_stream[i] += v
                    if collected is not None:
                        collected[i].append(rows.astype(np.uint8))
            dt = time.perf_counter() - t0 - warm
            seconds += dt
            n_blocks += 1
            if obs.enabled():
                m = obs.registry()
                m.counter("fleet.packets_total").inc(served)
                m.counter("fleet.chunks_total").inc()
                m.histogram("fleet.chunk_seconds").observe(dt)
    total = int(per_stream.sum())
    if obs.enabled() and seconds > 0:
        obs.registry().gauge("fleet.agg_pps").set(total / seconds)
    outputs = None
    if collected is not None:
        outputs = [
            np.concatenate(c, axis=0)
            if c
            else np.zeros((0, lowered.output_bits), np.uint8)
            for c in collected
        ]
    return FleetRunResult(
        streams=n_streams,
        packets=total,
        chunks=n_blocks,
        seconds=seconds,
        per_stream_packets=per_stream,
        bit_counts=bit_counts,
        outputs=outputs,
        warmup_seconds=warmup,
        backend=backend,
        devices=plan.devices or 1,
    )
