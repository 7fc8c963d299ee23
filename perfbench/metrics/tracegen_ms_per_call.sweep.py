"""Host time of generating and stacking one call's traces (the harness's
`bench.tracegen` span), mean over the traced calls."""


def read(ctx):
    if ctx.get("kind") != "sweep" or not ctx["tracegen_s"]:
        return None
    return 1e3 * sum(ctx["tracegen_s"]) / len(ctx["tracegen_s"])
