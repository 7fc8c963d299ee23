"""Self time of the serve tick's outcome phase (`repro.serve.outcome`:
per-lane sums, retries, state merge) per whole `repro.serve.tick` span of
the traced window, in ms."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    v = sr.per_root(sr.of(ctx), ["repro.serve.outcome"], [sr.SERVE_TICK])
    return None if v is None else 1e3 * v
