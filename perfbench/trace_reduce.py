"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace of a `--trace 1` run holds one `/device:TPU:<n>` plane per chip
with the lines "XLA Modules" (one event per executed program, named
`jit_<function>(<hash>)`) and "XLA Ops" (one event per executed HLO
instruction, named by its HLO text `%<instr> = ...`; a `while` loop's
event spans its body's events), and the host plane `/host:CPU`, whose
main-thread line carries the harness's `bench.*` annotations and the
JAX runtime's host events. Everything is reduced inside the `bench.window`
annotation:

  * busy: the union of the "XLA Ops" intervals, per chip;
  * scan: the top-level `%while` instructions (or a fused `epoch_step`
    kernel) inside the programs of the cell's entry point;
  * collectives: all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute instructions, on "XLA Ops" and "Async XLA Ops";
  * device_ops: self time (minus nested events) per instruction;
  * idle_gaps: every stretch with no op on a chip, named by the harness
    span and the host event that overlap it most.

The harness's thread is the host line that holds `bench.window` (it is
named after the process, "python" or "python3").
"""
from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent

COLLECTIVE = re.compile(r"^%(all-gather|all-reduce|reduce-scatter|"
                        r"all-to-all|collective-permute)")
SCAN = re.compile(r"^%while")
KERNEL = re.compile(r"epoch_step")
TOP = 10
# Idle stretches shorter than this (between the ops of one program) are
# summed under one name instead of being matched to host events.
SHORT_GAP_NS = 10_000.0
SHORT_GAP = "device: gaps under 10 us between ops"


def peaks_for(device_kind: str) -> dict:
    """The peaks table's row for a device kind; unknown kinds are an
    error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]


def instr_name(event_name: str) -> str:
    """'%fusion.36 = f32[...] fusion(...)' -> 'fusion.36'."""
    head = event_name.split(" = ", 1)[0]
    return head.lstrip("%")


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals) -> float:
    return sum(b - a for a, b in union(list(intervals)))


def self_times(events: List[Tuple[str, float, float]]) -> Dict[str, float]:
    """Per name, duration minus the time of events nested inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [end, name, child_time]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            end, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += b - a
        out[name] += b - a
        stack.append([b, name, 0.0])
    while stack:
        end, n, child = stack.pop()
        out[n] -= child
    return out


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def _host_thread(planes) -> list:
    """Events of the host thread that ran the harness: the line of
    `/host:CPU` holding the `bench.window` span (else the most `bench.*`
    spans)."""
    best, best_n = [], -1
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for e in line.events]
            n = sum(1 for e in ev if e[0].startswith("bench."))
            if any(e[0] == "bench.window" for e in ev):
                return ev
            if n > best_n:
                best, best_n = ev, n
    return best


def newest_xplane(directory: Path) -> Path:
    files = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(files[-1])


def reduce_dir(directory: Path, ctx: Optional[dict] = None) -> dict:
    return reduce_file(newest_xplane(directory), ctx)


def reduce_file(path: Path, ctx: Optional[dict] = None) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    py = _host_thread(planes)
    win = [(a, b) for n, a, b in py if n == "bench.window"]
    if win:
        lo, hi = win[0]
    else:                           # a trace recorded without the harness
        spans = [(a, b) for _, a, b in py]
        lo, hi = min(a for a, _ in spans), max(b for _, b in spans)
    devices = sorted((p for p in planes
                      if re.match(r"^/device:TPU:\d+$", p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    entry = (ctx or {}).get("entry_jit")
    per_dev = []
    ops_total: Dict[str, float] = defaultdict(float)
    gaps_total: Dict[str, float] = defaultdict(float)
    bench_spans = Spans([e for e in py if e[0].startswith("bench.")
                         and e[0] != "bench.window"])
    other_host = Spans([e for e in py if not e[0].startswith("bench.")])
    for plane in devices:
        ops = _clip(_line(plane, "XLA Ops"), lo, hi)
        mods = _clip(_line(plane, "XLA Modules"), lo, hi)
        asyncs = _clip(_line(plane, "Async XLA Ops"), lo, hi)
        busy = union([(a, b) for _, a, b in ops])
        # Scan time: top-level loops (or the kernel) inside the entry's
        # programs.
        entry_mods = [(a, b) for n, a, b in mods
                      if entry is not None and entry in n]
        # The union: a nested loop inside a counted one counts once.
        scan_iv = [(a, b) for n, a, b in ops
                   if (SCAN.match(n) or KERNEL.search(n))
                   and any(ma <= a and b <= mb for ma, mb in entry_mods)]
        scan = covered(scan_iv)
        coll_iv = [(a, b) for n, a, b in ops + asyncs if COLLECTIVE.match(n)]
        label = {}
        for n, a, b in ops:
            if n not in label:
                owner = next((mn for mn, ma, mb in mods
                              if ma <= a and b <= mb), "?")
                label[n] = f"{owner.split('(')[0]}/{instr_name(n)}"
        for n, t in self_times(ops).items():
            ops_total[label[n]] += t
        gaps = []
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        for a, b in gaps:
            name = SHORT_GAP if b - a < SHORT_GAP_NS \
                else _gap_label(a, b, bench_spans, other_host)
            gaps_total[name] += b - a
        per_dev.append({"busy_ns": sum(b - a for a, b in busy),
                        "scan_ns": scan, "collective_ns": covered(coll_iv),
                        "n_collectives": len(coll_iv)})
    n = max(len(per_dev), 1)
    window_ns = hi - lo
    device_ops = sorted(((k, v / n * 1e-9) for k, v in ops_total.items()),
                        key=lambda kv: -kv[1])[:TOP]
    idle_gaps = sorted(((k, v / n * 1e-9) for k, v in gaps_total.items()),
                       key=lambda kv: -kv[1])[:TOP]
    return {"window_s": window_ns * 1e-9,
            "busy_s": sum(d["busy_ns"] for d in per_dev) / n * 1e-9,
            "devices": per_dev,
            "scan_s": [d["scan_ns"] * 1e-9 for d in per_dev],
            "collective_s": [d["collective_ns"] * 1e-9 for d in per_dev],
            "n_collectives": [d["n_collectives"] for d in per_dev],
            "device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps]}


class Spans:
    """Host events, searchable by the interval they overlap most."""

    def __init__(self, events):
        self.ev = sorted(events, key=lambda e: e[1])
        self.starts = [e[1] for e in self.ev]
        self.max_end = []
        m = float("-inf")
        for _, _, b in self.ev:
            m = max(m, b)
            self.max_end.append(m)

    def most(self, a: float, b: float) -> str:
        import bisect
        best, name = 0.0, ""
        j = bisect.bisect_left(self.starts, b) - 1
        while j >= 0 and self.max_end[j] > a:
            n, c, d = self.ev[j]
            o = min(b, d) - max(a, c)
            if o > best:
                best, name = o, n
            j -= 1
        return name


def _gap_label(a, b, bench_spans: Spans, other_host: Spans) -> str:
    """The harness span that overlaps [a, b] most, and inside it the host
    event that overlaps most."""
    where = bench_spans.most(a, b) or "outside harness spans"
    what = other_host.most(a, b)
    return f"{where}: {what}" if what else where
