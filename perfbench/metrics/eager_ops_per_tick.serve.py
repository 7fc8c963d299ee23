"""Eager device dispatches per serve tick: the outermost `PjitFunction`
host events inside whole `repro.serve.tick` spans (the tick's own
program, `_session_tick_jit`, left out) per such span."""
import span_reduce as sr


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    return sr.per_root(sr.of(ctx), [sr.SERVE_TICK], [sr.SERVE_TICK],
                       "eager_ops")
