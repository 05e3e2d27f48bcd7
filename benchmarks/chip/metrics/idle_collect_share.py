"""``idle_collect_share``: the % of the traced window in which the chips
run no op while the program's innermost phase is ``collect`` (folding a
chunk's verdicts into the run's counts and outputs), averaged over the
chips the cell uses (``programspans.py``)."""
import programspans


def read(run):
    return programspans.idle_share(run, "collect")
