"""Host time of a server tick: its wall time minus the server's own
blocking dispatch clock (`SessionServer._dispatch_wall_s`), mean over the
traced ticks."""


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["host_s"]:
        return None
    return 1e3 * sum(ctx["host_s"]) / len(ctx["host_s"])
