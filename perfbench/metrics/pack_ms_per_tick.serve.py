"""Self time of the serve tick's pack phase (`repro.serve.pack`: the
lanes' next chunks stacked into the batch) per whole `repro.serve.tick`
span of the traced window, in ms."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    v = sr.per_root(sr.of(ctx), ["repro.serve.pack"], [sr.SERVE_TICK])
    return None if v is None else 1e3 * v
