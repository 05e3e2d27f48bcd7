"""``device_roofline_share``: the least time the chips could take for the
model's work on the packets verdicted in the traced window (``work.py``,
from the BNN shapes alone), over the time the chips were busy."""
import work


def read(run):
    if run.trace is None or run.trace.busy_s <= 0 or run.peaks is None:
        return None
    ops, nbytes = work.totals(
        [t["shape"] for t in run.cell.config["tenants"]],
        run.window.get("per_tenant_packets", [run.window["packets"]]),
    )
    least, _ = work.least_time(ops, nbytes, run.peaks)
    return 100.0 * least / (run.trace.busy_s * run.chips)
