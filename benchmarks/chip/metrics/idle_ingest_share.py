"""``idle_ingest_share``: the % of the traced window in which the chips run
no op while the program's innermost phase is ``ingest`` (pulling the next
chunk or block from its source, re-chunking, padding, filling a fleet
block), averaged over the chips the cell uses (``programspans.py``)."""
import programspans


def read(run):
    return programspans.idle_share(run, "ingest")
