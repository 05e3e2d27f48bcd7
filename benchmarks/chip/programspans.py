"""The chips' idle time in a traced window, split among the program's phases.

The program opens one span of each phase for every chunk it serves
(``ingest``, ``h2d``, ``dispatch``, ``d2h``, ``collect``; see
``repro.dataplane.executor.PHASES``), and ``repro.obs`` writes each into the
profiler's trace as a host event of that name with a ``chunk`` argument.
This module reads them from the trace the harness writes
(``harness.TRACE_DIR``):

* The window and its host thread are as in ``tracereduce``: the span of
  the ``window`` annotation, and the ``/host:CPU`` line that holds it.
* A phase event is an event of that line named for a phase that carries a
  ``chunk`` statistic; other events of the same name are not the
  program's.
* A chip's idle time is the window less the union of its ``XLA Ops``
  events.  Each stretch of it is put down to the innermost phase event
  open on the host thread then, or to none.
* A quantity's share is the idle time under its phases over the window,
  in %, averaged over the chips the cell uses, as ``device_idle_share``
  is: the shares of the quantities add up to ``device_idle_share`` less
  the idle time that no phase covers.

The trace is reduced once per run (cached by its file, size and time); a run
without a trace, a trace without phase events (a program that opens
none) and a trace without the cell's chips all read ``None``.
"""
from __future__ import annotations

from pathlib import Path

import tracereduce

PHASES = ("ingest", "h2d", "dispatch", "d2h", "collect")
QUANTITIES = {
    "ingest": ("ingest",),
    "transfer": ("h2d", "d2h"),
    "dispatch": ("dispatch",),
    "collect": ("collect",),
}
CHUNK_STAT = "chunk"

_CACHE: dict = {}


def _host_line(profile):
    """The window ``(lo, hi)`` and the events of the line that holds it."""
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = list(line.events)
            marks = [e for e in events if e.name == tracereduce.WINDOW]
            if marks:
                lo = min(e.start_ns for e in marks)
                hi = max(e.start_ns + e.duration_ns for e in marks)
                return (lo, hi), events
    return None, []


def _is_phase(event) -> bool:
    return event.name in PHASES and any(k == CHUNK_STAT for k, _ in event.stats)


def phase_segments(events, lo, hi) -> list:
    """Disjoint sorted ``(start, end, phase)`` stretches of ``[lo, hi]``,
    each under one innermost phase event."""
    spans = sorted(  # an enclosing span before the spans it holds
        ((e.start_ns, e.start_ns + e.duration_ns, e.name) for e in events if _is_phase(e)),
        key=lambda s: (s[0], -s[1]),
    )
    cuts = sorted({lo, hi, *(t for s, e, _ in spans for t in (s, e) if lo < t < hi)})
    pieces = list(zip(cuts, cuts[1:]))
    mids = [(s + e) / 2 for s, e in pieces]
    out = []
    for (s, e), ev in zip(pieces, tracereduce.innermost(spans, mids)):
        if ev is None:
            continue
        if out and out[-1][2] == ev[2] and out[-1][1] == s:
            out[-1] = (out[-1][0], e, ev[2])
        else:
            out.append((s, e, ev[2]))
    return out


def overlap_by_phase(holes, segments) -> dict:
    """Nanoseconds of the disjoint sorted ``holes`` under each phase of the
    disjoint sorted ``segments``."""
    out = dict.fromkeys(PHASES, 0.0)
    i = 0
    for s, e in holes:
        while i < len(segments) and segments[i][1] <= s:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < e:
            a, b, phase = segments[j]
            out[phase] += max(0.0, min(b, e) - max(a, s))
            j += 1
    return out


def idle_by_phase(profile, chips: int):
    """``{phase: % of the window}``, the chips' idle time under each phase
    averaged over the first ``chips`` TPUs; ``None`` where the trace has no
    window, no phase event in it, or no such chip."""
    window, events = _host_line(profile)
    if window is None:
        return None
    lo, hi = window
    segments = phase_segments(events, lo, hi)
    devices = sorted(
        (int(m.group(1)), p)
        for p in profile.planes
        if (m := tracereduce.DEVICE_PLANE.match(p.name))
    )[:chips]
    if not segments or not devices or hi <= lo:
        return None
    total = dict.fromkeys(PHASES, 0.0)
    for _, plane in devices:
        ops = [
            (max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
            for line in plane.lines
            if line.name == tracereduce.OPS_LINE
            for e in line.events
        ]
        busy = tracereduce.union([(s, e) for s, e in ops if e > s])
        holes = tracereduce.gaps(busy, lo, hi)
        for phase, ns in overlap_by_phase(holes, segments).items():
            total[phase] += ns
    return {p: 100.0 * ns / (hi - lo) / len(devices) for p, ns in total.items()}


def trace_dir() -> Path:
    import harness

    return harness.TRACE_DIR


def reduced(run):
    """``idle_by_phase`` of the run's trace, reduced once; ``None`` for an
    untraced run."""
    if run.trace is None:
        return None
    try:
        path = tracereduce.trace_file(trace_dir())
    except FileNotFoundError:
        return None
    key = (str(path), path.stat().st_size, path.stat().st_mtime_ns, run.chips)
    if key not in _CACHE:
        from jax.profiler import ProfileData

        _CACHE.clear()
        _CACHE[key] = idle_by_phase(ProfileData.from_file(str(path)), run.chips)
    return _CACHE[key]


def idle_share(run, quantity: str):
    """The % of the traced window in which the chips idle under
    ``quantity``'s phases (``QUANTITIES``), or ``None``."""
    shares = reduced(run)
    if shares is None:
        return None
    return sum(shares[p] for p in QUANTITIES[quantity])
