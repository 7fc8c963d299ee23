"""The program's own spans in a profiler trace, for the per-layer metrics
that read them.

The program wraps its phases in `jax.profiler.TraceAnnotation`s named
`repro.*` (`src/repro/runtime/spans.py`), so in the trace of a `--trace 1`
run they sit on the harness's host thread beside the JAX runtime's host
events, on the device planes' clock. Inside the `bench.window` span this
module reduces them to:

  * spans: per program span name, `count` (the spans lying whole inside
    the window), `total_s` and `self_s` (clipped to the window; self time
    is the duration minus the time of the program spans nested inside)
    and `eager_ops`: the outermost `PjitFunction(...)` host events inside
    the whole spans of that name where it is a root (no program span
    encloses it), the cell's own entry program left out;
  * idle_by_span: idle device time by the innermost span over it (a
    program span, else a harness `bench.*` span) and the host event that
    overlaps it most, top 10, as `<span>: <host event>`.

A program without spans leaves `spans` empty, and every reader of it
returns None. The harness's reduction (`trace_reduce.py`) reads the same
trace; this module only adds to it.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional

import harness
import trace_reduce as tr

PROGRAM = "repro."
PJIT = "PjitFunction("
OUTSIDE = "outside spans"
# The program's roots that make one call of a sweep entry point, and the
# entry points themselves (one per call).
SWEEP_CALL_ROOTS = ("repro.traffic.generate", "repro.sim.stack_traces",
                    "repro.sim.sweep_batch", "repro.sim.sweep_topology_batch")
SWEEP_ENTRIES = ("repro.sim.sweep_batch", "repro.sim.sweep_topology_batch")
SERVE_TICK = "repro.serve.tick"


def window(host: list) -> tuple:
    """The `bench.window` span, else the extent of the host events."""
    win = [(a, b) for n, a, b in host if n == "bench.window"]
    if win:
        return win[0]
    return min(a for _, a, _ in host), max(b for _, _, b in host)


def outermost(events: list) -> list:
    """The events no other event of the list encloses."""
    out, end = [], float("-inf")
    for e in sorted(events, key=lambda e: (e[1], -e[2])):
        if e[2] > end:
            out.append(e)
            end = e[2]
    return out


def innermost(events: list, lo: float, hi: float) -> list:
    """[lo, hi] cut into (a, b, name) pieces, each named by the innermost
    of the (nested) events over it, "" where none is."""
    out: List[tuple] = []
    stack: List[tuple] = []         # (end, name)
    t = lo

    def upto(x):
        nonlocal t
        if x > t:
            out.append((t, x, stack[-1][1] if stack else ""))
            t = x

    for name, a, b in sorted(tr._clip(events, lo, hi),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            upto(stack[-1][0])
            stack.pop()
        upto(a)
        stack.append((b, name))
    while stack:
        upto(stack[-1][0])
        stack.pop()
    upto(hi)
    return out


def span_table(host: list, lo: float, hi: float,
               entry_jit: Optional[str] = None) -> Dict[str, dict]:
    prog = [e for e in host if e[0].startswith(PROGRAM)]
    clipped = tr._clip(prog, lo, hi)
    table: Dict[str, dict] = {}
    for n, a, b in clipped:
        row = table.setdefault(n, {"count": 0, "total_s": 0.0,
                                   "self_s": 0.0, "eager_ops": 0})
        row["total_s"] += (b - a) * 1e-9
    for n, t in tr.self_times(clipped).items():
        table[n]["self_s"] = t * 1e-9
    for n, a, b in prog:
        if lo <= a and b <= hi:
            table[n]["count"] += 1
    roots = [e for e in outermost(prog) if lo <= e[1] and e[2] <= hi]
    starts = [a for _, a, _ in roots]
    skip = None if entry_jit is None else f"{PJIT}{entry_jit})"
    pjit = [e for e in host if e[0].startswith(PJIT) and e[0] != skip]
    for _, a, b in outermost(pjit):
        j = bisect.bisect_right(starts, a) - 1
        if j >= 0 and b <= roots[j][2]:
            table[roots[j][0]]["eager_ops"] += 1
    return table


def idle_by_span(planes, host: list, lo: float, hi: float) -> list:
    """Idle device time by innermost span and overlapping host event."""
    labelled = [e for e in host if e[0].startswith(PROGRAM)
                or (e[0].startswith("bench.") and e[0] != "bench.window")]
    pieces = innermost(labelled, lo, hi)
    starts = [a for a, _, _ in pieces]
    other = tr.Spans([e for e in host if not e[0].startswith(PROGRAM)
                      and not e[0].startswith("bench.")])
    devices = [p for p in planes if re.match(r"^/device:TPU:\d+$", p.name)]
    total: Dict[str, float] = defaultdict(float)
    for plane in devices:
        busy = tr.union([(a, b) for _, a, b in
                         tr._clip(tr._line(plane, "XLA Ops"), lo, hi)])
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                _attribute(prev, a, pieces, starts, other, total)
            prev = max(prev, b)
    n = max(len(devices), 1)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:tr.TOP]
    return [[k, v / n * 1e-9] for k, v in top]


def _attribute(a, b, pieces, starts, other, total) -> None:
    if b - a < tr.SHORT_GAP_NS:
        total[tr.SHORT_GAP] += b - a
        return
    j = max(bisect.bisect_right(starts, a) - 1, 0)
    while j < len(pieces) and pieces[j][0] < b:
        pa, pb, name = pieces[j]
        x, y = max(a, pa), min(b, pb)
        if y > x:
            what = other.most(x, y)
            where = name or OUTSIDE
            total[f"{where}: {what}" if what else where] += y - x
        j += 1


def reduce_file(path: Path, entry_jit: Optional[str] = None) -> dict:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    host = tr._host_thread(planes)
    lo, hi = window(host)
    return {"window_s": (hi - lo) * 1e-9,
            "spans": span_table(host, lo, hi, entry_jit),
            "idle_by_span": idle_by_span(planes, host, lo, hi)}


# ---------------------------------------------------------------------------
# For the metric readers
# ---------------------------------------------------------------------------

def newest_trace(directory: Path) -> Optional[Path]:
    files = list(Path(directory).glob("**/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime_ns) if files else None


def of(ctx: dict) -> Optional[dict]:
    """`ctx["trace"]` with the program spans of the run's trace added:
    those of the newest trace the harness wrote (the run's own, checked
    by its window's length), reduced once per run. None without a trace.
    """
    trace = ctx.get("trace") or {}
    if "spans" in trace:
        return trace
    if not trace.get("window_s"):
        return None
    path = newest_trace(harness.CACHE_DIR / "trace")
    if path is None:
        return None
    red = reduce_file(path, ctx.get("entry_jit"))
    if abs(red["window_s"] - trace["window_s"]) > 1e-9:
        red = {"spans": {}, "idle_by_span": []}      # another run's trace
    trace.update(spans=red["spans"], idle_by_span=red["idle_by_span"])
    if red["spans"]:
        print("[spans] " + json.dumps(red), file=sys.stderr, flush=True)
    return trace


def per_root(red: Optional[dict], names: Iterable[str],
             roots: Iterable[str], field: str = "self_s") -> Optional[float]:
    """`field` summed over the spans `names`, per whole span of `roots`;
    None where the trace holds no whole root."""
    if red is None:
        return None
    spans = red["spans"]
    n = sum(spans[r]["count"] for r in roots if r in spans)
    if n == 0:
        return None
    return sum(spans[k][field] for k in names if k in spans) / n
