"""``recirc_passes``: the recirculation passes each packet of the traced
window took, read from the ``passes`` argument of the program's
``dispatch`` phase spans in the window (the trace as ``programspans.py``
reads it); ``None`` for an untraced run, or where no such span carries
the argument."""
import programspans
import tracereduce


def passes_in(profile):
    """The most ``passes`` any ``dispatch`` phase span of the window
    carries, or ``None``."""
    window, events = programspans._host_line(profile)
    if window is None:
        return None
    lo, hi = window
    found = [
        int(value)
        for e in events
        if e.name == "dispatch" and programspans._is_phase(e) and lo <= e.start_ns < hi
        for key, value in e.stats
        if key == "passes"
    ]
    return max(found) if found else None


def read(run):
    if run.trace is None:
        return None
    try:
        path = tracereduce.trace_file(programspans.trace_dir())
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData

    return passes_in(ProfileData.from_file(str(path)))
