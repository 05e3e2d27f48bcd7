"""``repro.obs`` — runtime observability for the dataplane hot path.

One global switch, plus two always-on live-view layers (``obs.windows``
sliding windows and ``obs.slo`` SLO burn-rate tracking — explicit-
timestamp, deterministic, owned by whoever instantiates them rather than
the global registry), and three switched capabilities:

* a **metrics registry** (``obs.metrics``): counters, gauges, and
  streaming histograms with p50/p95/p99 — packets/s, chunk latency,
  per-tenant queue delay, drops/defers, jit/table cache hits;
* a **span tracer** (``obs.tracing``): nested context-manager spans
  (``stream > chunk > hop > execute``) with explicit ``compile`` vs
  ``execute`` categories, exporting Chrome Trace Event JSON; while a JAX
  profiler session collects, every span is also written into the
  profiler's own trace (``jax.profiler.TraceAnnotation``), on the clock
  the device trace shares, whether or not the switch is on;
* a **lowering counter**: while the switch is on, every program JAX
  lowers counts into ``jax.lowerings_total`` and
  ``jax.lowering_seconds_total``, labelled with the innermost open span;
* **exporters** (``obs.export``): metrics JSONL, Prometheus-style text,
  chrome trace — rendered human-readable by ``tools/obs_report.py``.

Usage — instrumented code (the executor, fabric, scheduler, featurizer,
trainer) calls the module-level helpers, which are no-ops until
:func:`enable` flips the switch::

    from repro import obs

    with obs.span("execute:chunk", cat="execute", packets=n):
        ...hot work...
    if obs.enabled():
        obs.registry().counter("dataplane.packets_total").inc(n)

Operators (tests, benchmarks, examples, CI) turn it on around a run and
export::

    obs.enable(reset=True)
    ...traced run...
    paths = obs.export_all("obs_out")   # jsonl + prom + chrome trace

Invariants:

* **Disabled means no-op** — with the switch off and no profiler
  collecting, :func:`span` returns a shared null context manager and
  instrumented code skips all metric work; the instrumented paths are
  bit-exact with uninstrumented code in *both* states (observability
  never touches data), and the disabled-path overhead is bounded by test
  and benchmark (< 5%).
* **One global state** — helpers address a single process-wide registry +
  tracer pair, so instrumentation at any layer lands in one export.
  :func:`enable`'s ``reset=True`` starts a clean capture.
* **Import-light** — this package imports only stdlib + numpy; dataplane
  modules can instrument without import cycles.  JAX is looked up only
  once the process has imported it (no profiler can run before), and
  imported by :func:`enable` for the lowering listener.
"""
from __future__ import annotations

import os
import sys

from repro.obs import export as _export
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.slo import BreachEvent, SloSpec, SloStatus, SloTracker
from repro.obs.tracing import Span, SpanRecord, Tracer
from repro.obs.windows import WindowedHistogram, WindowedRate

__all__ = [
    "BreachEvent",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SloSpec",
    "SloStatus",
    "SloTracker",
    "Span",
    "SpanRecord",
    "Tracer",
    "WindowedHistogram",
    "WindowedRate",
    "disable",
    "enable",
    "enable_from_env",
    "enabled",
    "export_all",
    "registry",
    "reset",
    "span",
    "tracer",
]

OBS_ENV = "REPRO_OBS"           # truthy value enables at enable_from_env()
OBS_DIR_ENV = "REPRO_OBS_DIR"   # export directory for harnesses that honor it

_enabled = False
_registry = MetricsRegistry()
_tracer = Tracer()


class _NullSpan:
    """Shared do-nothing context manager — the disabled hot path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_TRACE_ME = None       # jax.profiler.TraceAnnotation, once JAX is imported
_listening = False      # _count_lowering is registered with jax.monitoring


def _profiler_collecting() -> bool:
    """Is a JAX profiler session collecting host events?"""
    global _TRACE_ME
    if _TRACE_ME is None:
        if "jax" not in sys.modules:  # no profiler runs before JAX does
            return False
        from jax.profiler import TraceAnnotation

        _TRACE_ME = TraceAnnotation
    return _TRACE_ME.is_enabled()


class _ProfiledSpan:
    """A profiler annotation around the tracer's span (``None`` while the
    switch is off): one phase, on both clocks."""

    __slots__ = ("_annotation", "_span")

    def __init__(self, annotation, span):
        self._annotation = annotation
        self._span = span

    def __enter__(self) -> "_ProfiledSpan":
        self._annotation.__enter__()
        if self._span is not None:
            self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._span is not None:
                self._span.__exit__(exc_type, exc, tb)
        finally:
            self._annotation.__exit__(exc_type, exc, tb)
        return False


def _count_lowering(event: str, duration: float, **kwargs) -> None:
    """``jax.monitoring`` listener: a lowering, under the calling thread's
    innermost open span."""
    if event != LOWERING_EVENT:
        return
    stack = _tracer._stack()
    where = stack[-1] if stack else "none"
    _registry.counter("jax.lowerings_total", span=where).inc()
    _registry.counter("jax.lowering_seconds_total", span=where).inc(duration)


def _listen_for_lowerings(on: bool) -> None:
    global _listening
    if on == _listening:
        return
    try:
        import jax.monitoring as monitoring
    except ImportError:
        return
    if on:
        monitoring.register_event_duration_secs_listener(_count_lowering)
    else:
        monitoring.unregister_event_duration_listener(_count_lowering)
    _listening = on


def enabled() -> bool:
    """Is the global observability switch on?"""
    return _enabled


def enable(*, reset: bool = False) -> None:
    """Turn observability on (``reset=True`` starts a clean capture) and
    start counting lowerings per span."""
    global _enabled
    if reset:
        globals()["_registry"] = MetricsRegistry()
        _tracer.reset()
    _enabled = True
    _listen_for_lowerings(True)


def disable() -> None:
    """Turn observability off (captured state is kept for export)."""
    global _enabled
    _enabled = False
    _listen_for_lowerings(False)


def reset() -> None:
    """Drop all captured metrics and spans (switch state unchanged)."""
    globals()["_registry"] = MetricsRegistry()
    _tracer.reset()


def enable_from_env() -> bool:
    """Enable iff ``$REPRO_OBS`` is set truthy; returns the switch state.

    The hook harnesses use (``benchmarks/run.py``, CI) so a job can opt a
    whole run into tracing without code changes.
    """
    val = os.environ.get(OBS_ENV, "").strip().lower()
    if val not in ("", "0", "false", "no", "off"):
        enable()
    return _enabled


def registry() -> MetricsRegistry:
    """The process-wide metrics registry."""
    return _registry


def tracer() -> Tracer:
    """The process-wide span tracer."""
    return _tracer


def span(name: str, cat: str = "span", **args):
    """A timed span when enabled, written into the profiler's trace (name
    and args) while a JAX profiler session collects; a shared no-op when
    neither holds."""
    if _profiler_collecting():
        return _ProfiledSpan(
            _TRACE_ME(name, **args),
            _tracer.span(name, cat, **args) if _enabled else None,
        )
    if not _enabled:
        return _NULL_SPAN
    return _tracer.span(name, cat, **args)


def export_all(out_dir: str, *, prefix: str = "obs") -> dict[str, str]:
    """Write metrics JSONL + Prometheus text + chrome trace to ``out_dir``
    (see ``repro.obs.export.export_all``); returns the artifact paths."""
    return _export.export_all(out_dir, _registry, _tracer, prefix=prefix)
