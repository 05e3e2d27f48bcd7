"""``idle_dispatch_share``: the % of the traced window in which the chips
run no op while the program's innermost phase is ``dispatch`` (the call
into the compiled step until it returns: tracing, lowering, cache loads,
argument sharding, enqueue), averaged over the chips the cell uses
(``programspans.py``)."""
import programspans


def read(run):
    return programspans.idle_share(run, "dispatch")
