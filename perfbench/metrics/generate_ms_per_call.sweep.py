"""Self time of trace generation (`repro.traffic.generate`) per traced
sweep call (whole entry-point spans of the window), in ms."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "sweep":
        return None
    v = sr.per_root(sr.of(ctx), ["repro.traffic.generate"], sr.SWEEP_ENTRIES)
    return None if v is None else 1e3 * v
