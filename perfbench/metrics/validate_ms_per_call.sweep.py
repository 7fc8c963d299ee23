"""Self time of trace validation (`repro.traffic.validate`, in
`stack_traces` and again at the entry point; its host reads wait on the
generator's device work) per traced sweep call, in ms."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "sweep":
        return None
    v = sr.per_root(sr.of(ctx), ["repro.traffic.validate"], sr.SWEEP_ENTRIES)
    return None if v is None else 1e3 * v
