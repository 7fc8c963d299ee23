"""Share of its roofline that the interval scan reaches: the least time
of the interval model's work (work_count.py, at the cell's shapes) over
the device time of the scan in the trace (trace_reduce.py), per chip."""
import work_count


def read(ctx):
    if ctx.get("kind") != "sweep":
        return None
    scan_s = sum(ctx["trace"]["scan_s"]) / max(len(ctx["trace"]["scan_s"]), 1)
    if scan_s <= 0 or ctx["calls"] == 0:
        return None
    shape = ctx["shape"]
    work = work_count.interval_work(
        traces=shape["traces"], trace_chiplets=shape["trace_chiplets"],
        intervals=shape["intervals"],
        chiplets_per_lane=shape["chiplets_per_lane"],
        gateways=shape["gateways"],
        memory_gateways=shape["memory_gateways"])
    least = work_count.least_seconds(work, ctx["peaks"], ctx["n_devices"])
    # The traced window holds `calls` whole calls plus, at most, the scans
    # of calls cut by its edges; scan_s is the scan time inside it.
    return 100.0 * least["seconds"] * ctx["calls"] / scan_s
