"""Eager device dispatches per sweep call: the outermost `PjitFunction`
host events inside the call's root spans (`repro.traffic.generate`,
`repro.sim.stack_traces`, the entry point; the entry's own program left
out) per traced call."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "sweep":
        return None
    return sr.per_root(sr.of(ctx), sr.SWEEP_CALL_ROOTS, sr.SWEEP_ENTRIES,
                       "eager_ops")
