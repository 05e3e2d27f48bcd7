"""Roofline probes for compiled dataplane executables.

Bridges the dormant HLO cost analyzer (``roofline.hlo``) into the
dataplane: AOT-lower the *exact* jitted function a stream/fleet run
dispatches, analyze its HLO for per-dispatch FLOPs and bytes, and turn
the roofline of the device it runs on (``roofline.hw``, keyed by
``device_kind``) into a **packets-per-second upper bound** — so "fast as
the hardware allows" is a number next to every measured rate.  Off-TPU no
published peak applies: the probe still records the HLO costs, but the
bound is infinite and no ``pps_bound``/``fraction`` gauge is published.  On
a TPU whose kind the table lacks, the probe raises.

A BNN dataplane executable has essentially no dot FLOPs (XNOR +
popcount lowers to elementwise integer ops), so MFU is meaningless here;
the honest hardware ceiling is the *memory* roofline:

    roofline_pps = packets_per_dispatch / max(bytes / HBM_BW,
                                              flops / PEAK_FLOPS,
                                              collective_bytes / ICI_BW)

and ``fraction = measured_pps / roofline_pps`` is the utilization number
the CI gate tracks (``dataplane_packed_roofline_frac``).

Probes are cached per (fingerprint, path, shape): lowering + HLO analysis
costs milliseconds but not nothing.  ``serving.engine.FleetEngine`` runs
the fleet probe at most once per compiled executable — in its warmup
window, never on the steady hot path, and only when ``repro.obs`` is
enabled — and reports it in ``health()``; ``record`` publishes a probe's
gauges.  The served loops (``executor.execute_stream``,
``fleet.execute_fleet``) run no probe.

Everything JAX-facing is imported lazily so this module stays importable
(and the analyzer usable on saved HLO text) without touching the
dataplane, and so ``repro.dataplane`` can import it without a cycle.
"""
from __future__ import annotations

import dataclasses
import math

from repro.roofline import hw
from repro.roofline.hlo import HloCosts, analyze

__all__ = [
    "DataplaneRoofline",
    "probe_fleet",
    "probe_stream",
    "record",
]

_CACHE: dict[tuple, "DataplaneRoofline"] = {}


@dataclasses.dataclass(frozen=True)
class DataplaneRoofline:
    """HLO costs + roofline bound for one compiled dataplane executable."""

    path: str            # e.g. "packed", "jnp", "packed+scan", "fleet64:packed"
    fingerprint: str     # LoweredProgram.fingerprint()
    chunk: int           # packets per stream per dispatch
    streams: int         # 1 for a single stream; N for a vmapped fleet
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    peaks: hw.ChipPeaks | None = None  # None: no published peak (off-TPU)

    @property
    def packets(self) -> int:
        """Packets per compiled dispatch."""
        return self.chunk * self.streams

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / self.peaks.flops_bf16 if self.peaks else 0.0

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / self.peaks.hbm_bw if self.peaks else 0.0

    @property
    def collective_s(self) -> float:
        if not self.peaks:
            return 0.0
        return self.collective_bytes / self.peaks.ici_link_bw

    @property
    def step_time_s(self) -> float:
        """Roofline dispatch time (perfect overlap of the three engines)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bottleneck(self) -> str:
        if not self.peaks:
            return "unknown"
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def roofline_pps(self) -> float:
        """Hardware packets/s ceiling for this executable."""
        t = self.step_time_s
        return self.packets / t if t > 0 else math.inf

    @property
    def bytes_per_packet(self) -> float:
        return self.hlo_bytes / self.packets if self.packets else 0.0

    def fraction(self, measured_pps: float) -> float:
        """measured / roofline — the utilization number the gate tracks."""
        bound = self.roofline_pps
        if not (measured_pps > 0) or not math.isfinite(bound) or bound <= 0:
            return 0.0
        return measured_pps / bound


def _build(key: tuple, path: str, lowered, chunk: int, streams: int,
           costs: HloCosts) -> "DataplaneRoofline":
    import jax

    rf = DataplaneRoofline(
        path=path,
        fingerprint=lowered.fingerprint(),
        chunk=chunk,
        streams=streams,
        hlo_flops=costs.flops,
        hlo_bytes=costs.bytes,
        collective_bytes=costs.collective_bytes,
        peaks=hw.device_peaks(jax.devices()[0]),
    )
    _CACHE[key] = rf
    return rf


def probe_stream(
    lowered,
    *,
    backend: str,
    chunk: int,
    interpret: bool | None = None,
    scan_hops: bool = False,
) -> DataplaneRoofline:
    """Roofline for one ``executor._run_chunk`` dispatch at ``chunk``
    packets — the executable ``execute`` / ``execute_stream`` runs.

    AOT-lowers the chunk path (parse -> hop -> deparse, the one cached jit
    ``_run_chunk`` dispatches) at ``chunk`` packets, so the analyzed HLO is
    the whole dispatch, not a part.
    """
    path = backend + ("+scan" if scan_hops else "")
    key = (lowered.fingerprint(), path, chunk, 1, interpret)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    import jax
    import jax.numpy as jnp

    from repro.dataplane import executor as _executor

    fn = _executor._chunk_fn(lowered, backend, interpret, scan_hops)
    spec = jax.ShapeDtypeStruct((chunk, lowered.input_bits), jnp.int32)
    costs = analyze(fn.lower(spec).compile().as_text())
    return _build(key, path, lowered, chunk, 1, costs)


def probe_fleet(
    lowered,
    *,
    backend: str,
    streams: int,
    chunk: int,
    interpret: bool | None = None,
    scan_hops: bool = False,
    devices: int | None = None,
) -> DataplaneRoofline:
    """Roofline for one vmapped fleet dispatch: ``streams`` streams of
    ``chunk`` packets through ``fleet.fleet_fn``'s compiled executable."""
    path = f"fleet{streams}:{backend}" + ("+scan" if scan_hops else "")
    key = (lowered.fingerprint(), path, chunk, streams, interpret, devices)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    import jax
    import jax.numpy as jnp

    from repro.dataplane import fleet as _fleet

    fn = _fleet.fleet_fn(
        lowered,
        backend=backend,
        interpret=interpret,
        scan_hops=scan_hops,
        devices=devices,
    )
    spec = jax.ShapeDtypeStruct((streams, chunk, lowered.input_bits), jnp.int32)
    costs = analyze(fn.lower(spec).compile().as_text())
    return _build(key, path, lowered, chunk, streams, costs)


def record(rf: DataplaneRoofline, measured_pps: float | None = None) -> None:
    """Publish a probe's costs (and utilization, when a measured rate is
    known) as ``roofline.*`` gauges in the global obs registry.

    ``FleetEngine`` calls it after a serve; a no-op when observability is
    off, so the disabled hot path stays untouched.
    """
    from repro import obs

    if not obs.enabled():
        return
    m = obs.registry()
    m.gauge("roofline.hlo_bytes", path=rf.path).set(rf.hlo_bytes)
    m.gauge("roofline.hlo_flops", path=rf.path).set(rf.hlo_flops)
    m.gauge("roofline.bytes_per_packet", path=rf.path).set(rf.bytes_per_packet)
    if rf.peaks is None:
        return
    m.gauge("roofline.pps_bound", path=rf.path).set(rf.roofline_pps)
    if measured_pps is not None and measured_pps > 0:
        m.gauge("roofline.fraction", path=rf.path).set(rf.fraction(measured_pps))
