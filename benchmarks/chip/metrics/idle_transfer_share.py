"""``idle_transfer_share``: the % of the traced window in which the chips
run no op while the program's innermost phase is ``h2d`` (the copy in) or
``d2h`` (the wait for the result and the copy back), averaged over the
chips the cell uses (``programspans.py``)."""
import programspans


def read(run):
    return programspans.idle_share(run, "transfer")
