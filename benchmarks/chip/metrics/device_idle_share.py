"""``device_idle_share``: 1 - (union of op intervals on the chips' op
lines) / traced window, averaged over the chips the cell uses."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
