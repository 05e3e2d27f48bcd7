"""Reduce a profiler trace of one window to busy time, idle gaps and ops.

The trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
one plane per TPU (``/device:TPU:<n>``), whose ``XLA Ops`` line has one
event per device operation, and a ``/host:CPU`` plane with a line per
host thread.  The harness wraps the window in a ``window`` annotation and
its calls in ``source.next`` and ``entry.run``.

* The window is the span of the ``window`` annotation.
* A chip's busy time is the union of its op intervals, clipped to the
  window; ``busy_s`` is the mean over the chips the cell uses.
* Idle gaps are the stretches of the window in which the first chip runs
  no op.  Each is labelled by what the window's host thread was doing at
  its midpoint: the innermost harness annotation and the innermost host
  event there, ``"entry.run:TransferFromDevice"`` say.
* The breakdown lists the ten device operations that took the most time
  over the chips (each named by the start of its HLO text), and the ten
  labels under which the most idle time fell.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

WINDOW = "window"
ANNOTATIONS = ("source.next", "entry.run")
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10
NAME_CHARS = 120  # an op's HLO text is cut to this for the breakdown


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over the chips used
    chip_busy_s: list
    ops: list                     # [(name, seconds)], summed over chips
    idle: list                    # [(label, seconds)], first chip

    def breakdown(self) -> dict:
        return {
            "device_ops": [[n[:NAME_CHARS], s] for n, s in self.ops[:TOP]],
            "idle_gaps": [[n, s] for n, s in self.idle[:TOP]],
        }


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(busy, lo, hi) -> list:
    """The stretches of ``[lo, hi]`` that the disjoint ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def innermost(events, points) -> list:
    """For each sorted point, the innermost of the nested ``(start, end,
    name)`` events (sorted by start) that covers it, or ``None``."""
    out, stack, i = [], [], 0
    for p in points:
        while i < len(events) and events[i][0] <= p:
            while stack and stack[-1][1] <= events[i][0]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        out.append(next((ev for ev in reversed(stack) if ev[1] > p), None))
    return out


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def reduce_profile(profile, chips: int) -> Reduced:
    planes = list(profile.planes)
    window, thread = None, []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = _events(line)
            marks = [ev for ev in evs if ev[2] == WINDOW]
            if marks:
                window = (min(m[0] for m in marks), max(m[1] for m in marks))
                thread = sorted(ev for ev in evs if ev[2] != WINDOW)
    if window is None:
        raise ValueError("the trace has no 'window' annotation")
    lo, hi = window
    devices = sorted(
        (int(m.group(1)), p) for p in planes if (m := DEVICE_PLANE.match(p.name))
    )[:chips]
    chip_busy, op_time, first_busy = [], {}, []
    for k, (_, plane) in enumerate(devices):
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for s, e, name in _events(line):
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    spans.append((s, e))
                    op_time[name] = op_time.get(name, 0.0) + (e - s) / 1e9
        busy = union(spans)
        chip_busy.append(sum(e - s for s, e in busy) / 1e9)
        if k == 0:
            first_busy = busy
    idle = {}
    holes = gaps(first_busy, lo, hi)
    mids = [(s + e) / 2 for s, e in holes]
    ours = sorted(ev for ev in thread if ev[2] in ANNOTATIONS)
    for (s, e), outer, inner in zip(holes, innermost(ours, mids), innermost(thread, mids)):
        label = outer[2] if outer else "host"
        if inner and inner[2] != label:
            label = f"{label}:{inner[2]}"
        idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(chip_busy) / len(chip_busy) if chip_busy else 0.0,
        chip_busy_s=chip_busy,
        ops=sorted(op_time.items(), key=lambda kv: -kv[1]),
        idle=sorted(idle.items(), key=lambda kv: -kv[1]),
    )


def trace_file(directory: Path) -> Path:
    files = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def reduce_file(path: Path, chips: int) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)), chips)


def reduce_dir(directory: Path, chips: int) -> Reduced:
    return reduce_file(trace_file(directory), chips)
