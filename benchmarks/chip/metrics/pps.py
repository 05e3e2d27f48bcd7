"""``pps``: packets verdicted over the whole window's wall time."""


def read(run):
    return run.window["packets"] / run.window["seconds"]
