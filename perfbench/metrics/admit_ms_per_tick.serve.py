"""Self time of the serve tick's admit phase (`repro.serve.admit`: queue
to lanes, fresh lane carries) per whole `repro.serve.tick` span of the
traced window, in ms."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    v = sr.per_root(sr.of(ctx), ["repro.serve.admit"], [sr.SERVE_TICK])
    return None if v is None else 1e3 * v
