"""``mfu_int8``: the model's ops on the packets verdicted in the traced
window, over the traced window times the chips times the int8 peak."""
import work


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or run.peaks is None:
        return None
    ops, _ = work.totals(
        [t["shape"] for t in run.cell.config["tenants"]],
        run.window.get("per_tenant_packets", [run.window["packets"]]),
    )
    return 100.0 * ops / (run.trace.window_s * run.chips * run.peaks.ops_int8)
