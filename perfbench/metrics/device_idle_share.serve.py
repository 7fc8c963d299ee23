"""Share of the traced window in which no operation ran on the chip
(1 - busy / window, mean over the chips), in serve cells."""


def read(ctx):
    if ctx.get("kind") != "serve" or ctx["trace"]["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["trace"]["window_s"])
