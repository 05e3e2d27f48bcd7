"""Serving engines: batched LM decode slots and the dataplane fleet pipeline.

Two engines share this module's deployment shape — a stream of units
classified/extended at a fixed batched rate:

* :class:`Engine` — LM continuous batching.  ``max_batch`` decode slots;
  requests are prefilled (cache seeded at prompt length, right-padded to
  the decode budget) and inserted into free slots; every step decodes ALL
  active slots in one batched ``decode_step`` call; finished sequences free
  their slot for the next queued request.  Single-cache-per-slot variant:
  the batched cache is a pytree whose batch dim is the slot axis.

* :class:`FleetEngine` — the dataplane's async chunk pipeline.  Packet
  featurization (pcap decode + header featurization runs at ~230k pps on
  the host, an order of magnitude under the packed executor) is the serving
  bottleneck if run inline, so a producer thread assembles ``(streams,
  chunk, bits)`` fleet blocks from the per-stream iterators into a bounded
  queue while the main thread dispatches the compiled
  ``repro.dataplane.fleet`` executable — ingest and execution overlap
  instead of alternating.  Bit-exactness is untouched (the pipeline only
  reorders *when* blocks are built, never their contents); the result
  reports ingest/execute/wall seconds so the overlap is measurable.
"""
from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.dataplane import fleet as _fleet
from repro.dataplane.lowering import LoweredProgram, lower_program
from repro.dataplane.plan import ExecutionPlan
from repro.models import decode_step, init_cache, prefill
from repro.obs.slo import BreachEvent, SloSpec, SloStatus, SloTracker
from repro.obs.windows import WindowedHistogram, WindowedRate


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # filled by the engine:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_batch: int = 4,
        max_len: int = 256,
        mesh=None,
        sampler: Optional[Callable] = None,
    ):
        if cfg.encoder_only:
            raise ValueError("encoder-only architectures cannot be served")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.mesh = mesh
        self.sampler = sampler or (lambda logits: jnp.argmax(logits, axis=-1))

        self.cache = init_cache(cfg, max_batch, max_len)
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)       # next write index
        self.slot_budget = np.zeros(max_batch, np.int32)
        self.last_token = np.zeros(max_batch, np.int32)
        self.queue: list[Request] = []
        self.completed: list[Request] = []

        self._decode = jax.jit(
            lambda p, t, c: decode_step(p, t, c, cfg, mesh=mesh)
        )
        self._prefill = jax.jit(
            lambda p, b: prefill(p, b, cfg, mesh=mesh)
        )

    # -- API -------------------------------------------------------------

    def submit(self, req: Request) -> None:
        self.queue.append(req)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Process until queue + slots drain (or step budget)."""
        for _ in range(max_steps):
            self._admit()
            if not any(s is not None for s in self.slots):
                if not self.queue:
                    break
                continue
            self._step()
        return self.completed

    # -- internals ---------------------------------------------------------

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.slots[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            s = len(req.prompt)
            logits, pcache = self._prefill(
                self.params, {"tokens": jnp.asarray(req.prompt[None, :])}
            )
            tok = int(np.asarray(self.sampler(logits))[0])
            req.output.append(tok)
            # EOS / budget may already hit on the prefill-sampled token
            if (req.eos_id is not None and tok == req.eos_id) or req.max_new_tokens <= 1:
                req.done = True
                self.completed.append(req)
                continue
            self._install(slot, pcache, s)
            self.slots[slot] = req
            self.slot_pos[slot] = s
            self.slot_budget[slot] = req.max_new_tokens - 1
            self.last_token[slot] = tok

    def _install(self, slot: int, pcache, prompt_len: int) -> None:
        """Copy a prefilled (batch=1, len=S) cache into the slot axis of the
        batched cache, right-padding the sequence axis to max_len."""

        def put(dst, src):
            if src.ndim == 0:
                return dst
            # src: (L, 1, S, ...) or (L, 1, ...); dst: (L, B, max_len, ...)
            pad = [(0, 0)] * src.ndim
            if src.ndim >= 3 and dst.shape[2] != src.shape[2]:
                pad[2] = (0, dst.shape[2] - src.shape[2])
                src = jnp.pad(src, pad)
            idx = (slice(None), slice(slot, slot + 1))
            return dst.at[idx].set(src.astype(dst.dtype))

        self.cache = jax.tree.map(put, self.cache, pcache)
        # index field lives per-cache (scalar): decode uses per-slot positions
        # via the max — single-sequence engines keep them aligned; mixed-length
        # slots decode against the padded region masked by position index.
        self.cache = _set_index(self.cache, int(max(self.slot_pos.max(), prompt_len)))

    def _step(self) -> None:
        tokens = jnp.asarray(self.last_token)
        logits, self.cache = self._decode(self.params, tokens, self.cache)
        next_tok = np.asarray(self.sampler(logits))
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(next_tok[slot])
            req.output.append(tok)
            self.slot_pos[slot] += 1
            self.slot_budget[slot] -= 1
            if (req.eos_id is not None and tok == req.eos_id) or (
                self.slot_budget[slot] <= 0
                or self.slot_pos[slot] >= self.max_len - 1
            ):
                req.done = True
                self.completed.append(req)
                self.slots[slot] = None


# ---------------------------------------------------------------------------
# Dataplane fleet serving: async ingest/execute pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FleetServeResult:
    """Outcome of a pipelined fleet serve.

    ``wall_seconds`` is end-to-end steady-state time (first-block warmup
    excluded, queue stalls included) — the honest serving number.
    ``ingest_seconds``/``execute_seconds`` are the per-side busy times; with
    perfect overlap ``wall ~= max(ingest, execute)``, serialized it would be
    their sum."""

    streams: int
    packets: int
    chunks: int
    wall_seconds: float
    ingest_seconds: float
    execute_seconds: float
    warmup_seconds: float
    per_stream_packets: np.ndarray
    outputs: list | None = None

    @property
    def packets_per_second(self) -> float:
        return (
            self.packets / self.wall_seconds
            if self.wall_seconds > 0
            else float("inf")
        )

    @property
    def overlap_ratio(self) -> float:
        """(ingest + execute) / wall — 1.0 is fully serialized, 2.0 is
        perfect two-stage overlap."""
        busy = self.ingest_seconds + self.execute_seconds
        return busy / self.wall_seconds if self.wall_seconds > 0 else 1.0


def _count_probe_error() -> None:
    """A roofline probe or gauge publication failed: the serve goes on, and
    ``roofline.probe_errors_total`` says so."""
    obs.registry().counter("roofline.probe_errors_total").inc()


def _probe_fleet_roofline(lowered, backend, n_streams, chunk, plan):
    """Fail-soft ``roofline.dataplane`` probe of the vmapped fleet dispatch
    that ``health()`` reports — obs-only bookkeeping, never allowed to
    affect an execution path."""
    try:
        from repro.roofline import dataplane as _roofline_dp

        return _roofline_dp.probe_fleet(
            lowered,
            backend=backend,
            streams=n_streams,
            chunk=chunk,
            interpret=plan.interpret,
            scan_hops=bool(plan.scan_hops),
            devices=plan.devices,
        )
    except Exception:  # noqa: BLE001 - observation must not break runs
        _count_probe_error()
        return None


def _record_roofline(roofline, measured_pps):
    """Fail-soft gauge publication for a probe (see ``_probe_fleet_roofline``)."""
    try:
        from repro.roofline import dataplane as _roofline_dp

        _roofline_dp.record(roofline, measured_pps=measured_pps)
    except Exception:  # noqa: BLE001 - observation must not break runs
        _count_probe_error()


@dataclasses.dataclass(frozen=True)
class FleetHealth:
    """Live ``FleetEngine`` snapshot as of an explicit ``now``.

    The windowed fields (aggregate and per-stream pps, chunk-latency p99,
    SLO posture) come from the engine's explicit-timestamp windows — with
    an injected deterministic ``clock`` they are a pure function of the
    served blocks, which is what the determinism tests pin.
    ``queue_depth`` is the one genuinely live field: the number of
    assembled blocks waiting in the ingest queue at call time.
    """

    now: float
    streams: int
    queue_depth: int                   # blocks waiting in the ingest queue
    queue_capacity: int
    chunks: int                        # blocks dispatched since construction
    packets: int                       # packets served since construction
    windowed_pps: float                # aggregate rate over the trailing window
    per_stream_pps: tuple[float, ...]  # same, per fleet stream
    chunk_p99_s: float | None          # windowed p99 dispatch latency
    overlap_ratio: float | None        # last completed serve() (None before)
    slo: SloStatus | None              # None when no SLO was configured
    breach_events: tuple[BreachEvent, ...]
    roofline_pps_bound: float | None   # hardware ceiling of the compiled fn
    roofline_fraction: float | None    # windowed_pps / bound

    def render(self) -> str:
        lines = [
            f"fleet health @ {self.now:.3f}: {self.streams} stream(s), "
            f"queue {self.queue_depth}/{self.queue_capacity}, "
            f"{self.chunks} chunk(s) / {self.packets} packet(s) served",
            f"  windowed: {self.windowed_pps:.4g} pps aggregate, "
            f"chunk p99 "
            + (f"{self.chunk_p99_s * 1e3:.3f}ms"
               if self.chunk_p99_s is not None else "-"),
        ]
        if self.roofline_pps_bound is not None:
            frac = (
                f" ({self.roofline_fraction:.2e} of bound)"
                if self.roofline_fraction is not None else ""
            )
            lines.append(
                f"  roofline: {self.roofline_pps_bound:.4g} pps bound{frac}"
            )
        if self.slo is not None:
            s = self.slo
            burns = []
            if s.delay_burn_rate is not None:
                burns.append(f"delay burn {s.delay_burn_rate:.2f}x")
            if s.pps_burn_rate is not None:
                burns.append(f"pps burn {s.pps_burn_rate:.2f}x")
            state = "BREACHED" if s.breached else "ok"
            lines.append(
                f"  slo[{s.tenant}]: {state} "
                + (", ".join(burns) if burns else "no data")
                + f", {len(self.breach_events)} breach event(s)"
            )
        return "\n".join(lines)


class FleetEngine:
    """Async fleet pipeline: featurize/assemble blocks on a producer thread
    while the main thread runs the compiled fleet executable.

    ``plan`` carries backend/chunk/fleet/devices exactly as in
    ``repro.dataplane.run``; ``queue_depth`` bounds how many assembled
    blocks may wait (bounded memory even when ingest outruns execution).

    ``health()`` is the live snapshot API: sliding-window pps (aggregate
    and per stream), queue depth, chunk-latency p99, and — when an
    :class:`~repro.obs.slo.SloSpec` is passed — SLO burn rates and breach
    events.  All window/SLO timestamps come from ``clock`` (default
    ``time.perf_counter``), which is called only on the main dispatch
    thread, once per served block: inject a deterministic clock and every
    windowed health field becomes reproducible bit-for-bit.
    """

    def __init__(
        self,
        program,
        *,
        plan: ExecutionPlan | None = None,
        queue_depth: int = 4,
        slo: SloSpec | None = None,
        clock: Callable[[], float] | None = None,
        window_s: float = 10.0,
        window_buckets: int = 10,
    ):
        self.lowered = (
            program
            if isinstance(program, LoweredProgram)
            else lower_program(program)
        )
        self.plan = plan or ExecutionPlan()
        self.backend = _fleet._executor.resolve_backend(self.plan.backend_str)
        self.chunk = self.plan.chunk_size or _fleet.DEFAULT_STREAM_CHUNK
        self.queue_depth = queue_depth
        self.fn = _fleet.fleet_fn(
            self.lowered,
            backend=self.backend,
            interpret=self.plan.interpret,
            scan_hops=bool(self.plan.scan_hops),
            devices=self.plan.devices,
        )
        # -- health-snapshot state (explicit-timestamp windows + SLO) -------
        self._clock = clock or time.perf_counter
        self.window_s = float(window_s)
        self._window_buckets = int(window_buckets)
        self._agg_rate = WindowedRate(self.window_s, buckets=window_buckets)
        self._chunk_delay = WindowedHistogram(
            self.window_s, buckets=window_buckets
        )
        self._stream_rates: list[WindowedRate] = []
        self._slo = SloTracker(slo, buckets=window_buckets) if slo else None
        self._queue: _queue.Queue | None = None
        self._chunks_total = 0
        self._packets_total = 0
        self._last_result: FleetServeResult | None = None
        self._roofline = None
        self._last_now = 0.0

    def health(self, now: float | None = None) -> FleetHealth:
        """The live engine snapshot (see class docstring).  ``now`` defaults
        to the engine clock; pass the timestamp explicitly to re-read a
        window at a known instant (the deterministic-testing path)."""
        if now is None:
            now = self._clock()
        q = self._queue
        windowed = self._agg_rate.rate(now)
        rf = self._roofline
        bound = rf.roofline_pps if rf is not None and rf.peaks else None
        return FleetHealth(
            now=now,
            streams=len(self._stream_rates),
            queue_depth=q.qsize() if q is not None else 0,
            queue_capacity=self.queue_depth,
            chunks=self._chunks_total,
            packets=self._packets_total,
            windowed_pps=windowed,
            per_stream_pps=tuple(
                r.rate(now) for r in self._stream_rates
            ),
            chunk_p99_s=self._chunk_delay.p99(now),
            overlap_ratio=(
                self._last_result.overlap_ratio
                if self._last_result is not None else None
            ),
            slo=self._slo.status(now) if self._slo is not None else None,
            breach_events=(
                tuple(self._slo.events) if self._slo is not None else ()
            ),
            roofline_pps_bound=bound,
            roofline_fraction=(
                windowed / bound if bound else None
            ),
        )

    def _observe_block(self, now: float, dt: float, valid, served: int) -> None:
        """Fold one dispatched block into the health windows (main thread
        only; ``now`` comes from the injectable engine clock)."""
        self._last_now = now
        self._chunks_total += 1
        self._packets_total += served
        self._agg_rate.add(now, served)
        self._chunk_delay.observe(now, dt, count=1)
        for i, rate in enumerate(self._stream_rates):
            v = int(valid[i])
            if v:
                rate.add(now, v)
        if self._slo is not None:
            self._slo.observe_packets(now, served)
            # One fused dispatch serves the whole block: every packet in it
            # waits exactly the dispatch latency.
            self._slo.observe_queue_delay(now, dt, count=served)
            self._slo.update(now)

    def serve(self, streams, *, collect: bool = False) -> FleetServeResult:
        """Drain every stream through the pipelined fleet; bit-exact per
        stream with ``executor.execute`` (the pipeline reorders block
        *assembly*, never block contents)."""
        its = _fleet._normalize_streams(streams, self.plan.fleet)
        n_streams = len(its)
        if self.plan.devices is not None and n_streams % self.plan.devices:
            raise ValueError(
                f"fleet of {n_streams} streams does not shard evenly over "
                f"{self.plan.devices} devices"
            )
        if len(self._stream_rates) != n_streams:  # fleet size changed: reset
            self._stream_rates = [
                WindowedRate(self.window_s, buckets=self._window_buckets)
                for _ in range(n_streams)
            ]
        q: _queue.Queue = _queue.Queue(maxsize=self.queue_depth)
        self._queue = q
        ingest = [0.0]
        errors: list[BaseException] = []

        def produce() -> None:
            try:
                mark = time.perf_counter()
                for block in _fleet.fleet_blocks(
                    its, self.chunk, self.lowered.input_bits
                ):
                    # Time spent *building* the block (featurization, pcap
                    # pulls, re-chunking) — not time blocked on a full queue.
                    ingest[0] += time.perf_counter() - mark
                    q.put(block)
                    mark = time.perf_counter()
            except BaseException as e:  # surfaced after join
                errors.append(e)
            finally:
                q.put(None)

        per_stream = np.zeros(n_streams, np.int64)
        collected = [[] for _ in range(n_streams)] if collect else None
        execute_seconds = 0.0
        warmup = 0.0
        n_blocks = 0
        producer = threading.Thread(target=produce, name="fleet-ingest")
        with obs.span(
            "stream:fleet_serve", cat="stream",
            streams=n_streams, backend=self.backend, chunk_size=self.chunk,
        ):
            producer.start()
            t_start = time.perf_counter()
            while True:
                item = q.get()
                if item is None:
                    break
                blocks, valid = item
                dev = jnp.asarray(blocks)
                if n_blocks == 0:  # warm the compile cache outside the clock
                    with obs.span(
                        "compile:fleet_chunk", cat="compile",
                        streams=n_streams,
                    ):
                        w0 = time.perf_counter()
                        self.fn(dev).block_until_ready()
                        warmup = time.perf_counter() - w0
                    if obs.enabled():  # cost the compiled dispatch, once
                        self._roofline = _probe_fleet_roofline(
                            self.lowered, self.backend, n_streams,
                            self.chunk, self.plan,
                        )
                served_now = int(valid.sum())
                with obs.span(
                    "execute:fleet_chunk", cat="execute",
                    packets=served_now,
                ):
                    # Health observations use the engine clock on both sides
                    # of the dispatch (two calls per block, main thread only)
                    # so an injected deterministic clock makes every windowed
                    # health field reproducible; wall-clock bookkeeping for
                    # the serve result stays on perf_counter.
                    h0 = self._clock()
                    t0 = time.perf_counter()
                    res = np.asarray(self.fn(dev))
                    execute_seconds += time.perf_counter() - t0
                    h1 = self._clock()
                n_blocks += 1
                self._observe_block(h1, max(h1 - h0, 0.0), valid, served_now)
                for i in range(n_streams):
                    v = int(valid[i])
                    if not v:
                        continue
                    per_stream[i] += v
                    if collected is not None:
                        collected[i].append(res[i, :v].astype(np.uint8))
            wall = time.perf_counter() - t_start - warmup
            producer.join()
        if errors:
            raise errors[0]
        total = int(per_stream.sum())
        if obs.enabled() and wall > 0:
            obs.registry().gauge("fleet.serve_pps").set(total / wall)
            if self._roofline is not None:
                _record_roofline(self._roofline, total / wall)
        outputs = None
        if collected is not None:
            outputs = [
                np.concatenate(c, axis=0)
                if c
                else np.zeros((0, self.lowered.output_bits), np.uint8)
                for c in collected
            ]
        result = FleetServeResult(
            streams=n_streams,
            packets=total,
            chunks=n_blocks,
            wall_seconds=wall,
            ingest_seconds=ingest[0],
            execute_seconds=execute_seconds,
            warmup_seconds=warmup,
            per_stream_packets=per_stream,
            outputs=outputs,
        )
        self._last_result = result
        return result


def _set_index(cache, value: int):
    import dataclasses as dc

    def fix(obj):
        if hasattr(obj, "index") and dc.is_dataclass(obj):
            kw = {}
            for f in dc.fields(obj):
                v = getattr(obj, f.name)
                if f.name == "index":
                    kw[f.name] = jnp.asarray(value, jnp.int32)
                elif dc.is_dataclass(v):
                    kw[f.name] = fix(v)
                else:
                    kw[f.name] = v
            return dc.replace(obj, **kw)
        return obj

    return fix(cache)
