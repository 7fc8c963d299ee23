"""Host time of the sweep entry call, from the call to its asynchronous
return (the harness's `bench.entry` span), mean over the traced calls."""


def read(ctx):
    if ctx.get("kind") != "sweep" or not ctx["entry_s"]:
        return None
    return 1e3 * sum(ctx["entry_s"]) / len(ctx["entry_s"])
