"""``compiles_in_window``: programs lowered inside the window (a jit cache
miss, compiled or loaded from the persistent cache); should be 0."""


def read(run):
    if run.trace is None:
        return None
    return run.compiles
