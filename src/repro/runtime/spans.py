"""Host spans of the program, on the JAX profiler's clock.

Each span is a `jax.profiler.TraceAnnotation` named `repro.<name>`. With
no profiler running a span records nothing and costs about a microsecond;
under `jax.profiler.trace(dir)` (or `start_trace` / `stop_trace`) the
spans land in the trace's host plane, on the same clock as the device
planes, so a device's idle stretch can be read against the phase of the
program that left it idle.

Root spans (a server tick, a submission, an entry-point call) carry an
id: `tick=<n>`, `session=<id>`, or `call=<n>` from a per-process counter.
A span opened inside a root carries the root's id, so the spans of one
tick or one call can be grouped.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools

from jax.profiler import TraceAnnotation

PREFIX = "repro."

# Every span the program emits.
SPANS = (
    "repro.serve.tick",          # SessionServer.tick, whole
    "repro.serve.housekeep",     #   expire, complete drained, evict idle
    "repro.serve.admit",         #   queue -> lanes, fresh lane carries
    "repro.serve.pack",          #   lanes' next chunks -> the [B, T] batch
    "repro.serve.dispatch",      #   session_tick through block_until_ready
    "repro.serve.outcome",       #   per-lane sums, retries, state merge
    "repro.serve.observe",       #   degradation detector and heal
    "repro.serve.submit",        # SessionServer.submit: validate, chunk, pad
    "repro.traffic.generate",    # traffic.generate
    "repro.traffic.validate",    # traffic.validate_trace on concrete arrays
    "repro.sim.stack_traces",    # simulator.stack_traces
    "repro.sim.fetch_traces",    #   one device_get of concrete traces
    "repro.sim.sweep_batch",     # simulator.sweep_batch
    "repro.sim.sweep_topology_batch",   # simulator.sweep_topology_batch
    "repro.sim.dispatch",        #   the entry's jitted call
)

_ROOT_IDS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span_root_ids", default={})
_calls = itertools.count()


def span(name: str, **ids) -> TraceAnnotation:
    """A span inside a root: carries `ids`, else the enclosing root's."""
    return TraceAnnotation(PREFIX + name, **(ids or _ROOT_IDS.get()))


@contextlib.contextmanager
def root(name: str, **ids):
    """A root span; without `ids` it carries `call=<n>`, a per-process
    call number. Also usable as a decorator (a fresh number per call)."""
    ids = ids or {"call": next(_calls)}
    token = _ROOT_IDS.set(ids)
    try:
        with TraceAnnotation(PREFIX + name, **ids):
            yield
    finally:
        _ROOT_IDS.reset(token)
