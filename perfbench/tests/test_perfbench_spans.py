"""The program-span reduction (`span_reduce.py`) and the per-layer metrics
that read it: arithmetic on hand-built spans and events, and two small
profiler traces recorded on a TPU v5e. `tiny_serve` is two ticks and one
submission of a 2-lane `SessionServer` (table1 cell, 8-interval chunks)
run by the harness's serve runner under its annotations (`bench.window`,
`bench.tick`, `bench.client`) and the program's spans; `tiny_sweep`
predates the spans."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import span_reduce  # noqa: E402


def span_row(count=0, self_s=0.0, eager_ops=0):
    return {"count": count, "total_s": self_s, "self_s": self_s,
            "eager_ops": eager_ops}


SERVE = {"kind": "serve", "trace": {"window_s": 2.0, "spans": {
    "repro.serve.tick": span_row(4, 0.04, 800),
    "repro.serve.outcome": span_row(4, 0.32),
    "repro.serve.pack": span_row(4, 0.02),
    "repro.serve.admit": span_row(3, 0.01),
    "repro.serve.submit": span_row(5, 0.05, 40)}}}
SWEEP = {"kind": "sweep", "trace": {"window_s": 2.0, "spans": {
    "repro.traffic.generate": span_row(16, 0.012, 16),
    "repro.traffic.validate": span_row(18, 0.03),
    "repro.sim.stack_traces": span_row(2, 0.002, 4),
    "repro.sim.sweep_batch": span_row(2, 0.004, 6),
    "repro.sim.dispatch": span_row(2, 0.001)}}}


@pytest.mark.parametrize("metric, ctx, want", [
    ("outcome_ms_per_tick.serve", SERVE, 80.0),
    ("pack_ms_per_tick.serve", SERVE, 5.0),
    ("admit_ms_per_tick.serve", SERVE, 2.5),
    ("eager_ops_per_tick.serve", SERVE, 200.0),
    ("generate_ms_per_call.sweep", SWEEP, 6.0),
    ("validate_ms_per_call.sweep", SWEEP, 15.0),
    ("eager_ops_per_call.sweep", SWEEP, 13.0),
])
def test_reader_means_per_root(metric, ctx, want):
    read = harness.load_module("metrics", metric).read
    assert read(ctx) == pytest.approx(want, rel=1e-12)
    other = SWEEP if ctx is SERVE else SERVE
    assert read(other) is None


@pytest.mark.parametrize("metric", [
    "outcome_ms_per_tick.serve", "eager_ops_per_tick.serve",
    "generate_ms_per_call.sweep", "eager_ops_per_call.sweep"])
def test_reader_of_a_program_without_spans_is_none(metric):
    """The parent program emits no spans: its traced runs read nothing."""
    read = harness.load_module("metrics", metric).read
    kind = metric.rsplit(".", 1)[1]
    assert read({"kind": kind, "trace": {"window_s": 2.0,
                                         "spans": {}}}) is None


def test_a_phase_that_never_ran_reads_zero():
    spans = {"repro.serve.tick": span_row(2, 0.1)}
    read = harness.load_module("metrics", "admit_ms_per_tick.serve").read
    assert read({"kind": "serve", "trace": {"window_s": 1.0,
                                            "spans": spans}}) == 0.0


def test_outermost_keeps_only_enclosing_events():
    ev = [("a", 0, 10), ("b", 2, 4), ("a", 11, 12), ("c", 11.5, 11.8),
          ("d", 20, 30)]
    assert span_reduce.outermost(ev) == [("a", 0, 10), ("a", 11, 12),
                                         ("d", 20, 30)]


def test_innermost_names_each_piece_by_the_deepest_span():
    ev = [("root", 10, 50), ("child", 20, 30), ("leaf", 22, 25),
          ("next", 60, 70)]
    assert span_reduce.innermost(ev, 0, 80) == [
        (0, 10, ""), (10, 20, "root"), (20, 22, "child"), (22, 25, "leaf"),
        (25, 30, "child"), (30, 50, "root"), (50, 60, ""), (60, 70, "next"),
        (70, 80, "")]
    # Clipped to the window.
    assert span_reduce.innermost(ev, 24, 40) == [
        (24, 25, "leaf"), (25, 30, "child"), (30, 40, "root")]


def test_span_table_self_time_count_and_eager_ops():
    host = [("bench.window", 0, 100),
            ("repro.serve.tick", 10, 50), ("repro.serve.outcome", 20, 40),
            ("PjitFunction(dynamic_slice)", 21, 22),
            ("PjitFunction(dynamic_slice)", 21.1, 21.9),   # nested: once
            ("PjitFunction(_session_tick_jit)", 15, 16),   # the entry: out
            ("PjitFunction(squeeze)", 41, 42),
            ("repro.serve.tick", 90, 110),                 # cut by the window
            ("PjitFunction(squeeze)", 95, 96),
            ("PjitFunction(squeeze)", 60, 61)]             # outside spans
    t = span_reduce.span_table(host, 0, 100, "_session_tick_jit")
    assert t["repro.serve.tick"]["count"] == 1
    assert t["repro.serve.tick"]["total_s"] == pytest.approx(50e-9)
    assert t["repro.serve.tick"]["self_s"] == pytest.approx(30e-9)
    assert t["repro.serve.outcome"]["self_s"] == pytest.approx(20e-9)
    assert t["repro.serve.tick"]["eager_ops"] == 2
    assert t["repro.serve.outcome"]["eager_ops"] == 0


def test_per_root_needs_a_whole_root():
    assert span_reduce.per_root(None, ["x"], ["y"]) is None
    red = {"spans": {"y": span_row(0, 1.0)}}
    assert span_reduce.per_root(red, ["y"], ["y"]) is None


# ---------------------------------------------------------------------------
# Traces recorded on a TPU v5e
# ---------------------------------------------------------------------------

DATA = Path(__file__).resolve().parent / "data"


def unpack(tmp_path_factory, name):
    import gzip
    import shutil
    d = tmp_path_factory.mktemp(name) / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(DATA / f"{name}.xplane.pb.gz") as src, \
            open(d / "tpu.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return d / "tpu.xplane.pb"


def test_a_trace_without_program_spans_reads_empty(tmp_path_factory):
    """The sweep trace predates the spans: no span, idle time only under
    the harness's spans."""
    red = span_reduce.reduce_file(unpack(tmp_path_factory, "tiny_sweep"),
                                  "_sweep_batch_jit")
    assert red["spans"] == {}
    assert red["idle_by_span"]
    assert all(n.startswith("bench.") or n == span_reduce.tr.SHORT_GAP
               for n, _ in red["idle_by_span"])
    for metric in ("generate_ms_per_call.sweep",
                   "validate_ms_per_call.sweep", "eager_ops_per_call.sweep"):
        read = harness.load_module("metrics", metric).read
        assert read({"kind": "sweep", "trace": dict(red)}) is None


@pytest.fixture(scope="module")
def tiny_serve(tmp_path_factory):
    path = unpack(tmp_path_factory, "tiny_serve")
    return {"spans": span_reduce.reduce_file(path, "_session_tick_jit"),
            "harness": span_reduce.tr.reduce_file(
                path, {"entry_jit": "_session_tick_jit"})}


def test_tiny_serve_span_table(tiny_serve):
    red = tiny_serve["spans"]
    assert red["window_s"] == pytest.approx(0.081307704, rel=1e-9)
    sp = red["spans"]
    assert set(sp) == {
        "repro.serve.tick", "repro.serve.housekeep", "repro.serve.admit",
        "repro.serve.pack", "repro.serve.dispatch", "repro.serve.outcome",
        "repro.serve.observe", "repro.serve.submit", "repro.traffic.validate"}
    assert {k: v["count"] for k, v in sp.items()} == {
        "repro.serve.tick": 2, "repro.serve.housekeep": 2,
        "repro.serve.admit": 2, "repro.serve.pack": 4,
        "repro.serve.dispatch": 2, "repro.serve.outcome": 2,
        "repro.serve.observe": 2, "repro.serve.submit": 1,
        "repro.traffic.validate": 4}
    assert sp["repro.serve.tick"]["total_s"] == pytest.approx(
        0.073600065, rel=1e-9)
    assert sp["repro.serve.tick"]["self_s"] == pytest.approx(
        0.000495211, rel=1e-9)
    assert sp["repro.serve.outcome"]["self_s"] == pytest.approx(
        0.040120187, rel=1e-9)
    assert sp["repro.serve.admit"]["self_s"] == pytest.approx(
        0.024529139, rel=1e-9)
    assert sp["repro.serve.submit"]["self_s"] == pytest.approx(
        0.00736331, rel=1e-9)
    for row in sp.values():
        assert row["self_s"] <= row["total_s"] * (1 + 1e-12)


def test_tiny_serve_eager_ops_sit_under_the_roots(tiny_serve):
    sp = tiny_serve["spans"]["spans"]
    assert sp["repro.serve.tick"]["eager_ops"] == 156
    assert sp["repro.serve.submit"]["eager_ops"] == 16
    assert all(v["eager_ops"] == 0 for k, v in sp.items()
               if k not in ("repro.serve.tick", "repro.serve.submit"))


def test_tiny_serve_idle_time_by_span(tiny_serve):
    red, har = tiny_serve["spans"], tiny_serve["harness"]
    idle = har["window_s"] - har["busy_s"]
    by_span = dict(red["idle_by_span"])
    assert red["idle_by_span"][0] == [
        "repro.serve.outcome: PjitFunction(dynamic_slice)",
        pytest.approx(0.031888124, rel=1e-9)]
    assert by_span["repro.serve.admit: PjitFunction(dynamic_slice)"] == \
        pytest.approx(0.012201612, rel=1e-9)
    assert sum(by_span.values()) <= idle * (1 + 1e-9)
    phases = sum(v for k, v in by_span.items()
                 if k.startswith("repro.serve.")
                 and not k.startswith("repro.serve.tick"))
    assert phases > 0.8 * idle


def test_tiny_serve_harness_reduction_still_names_harness_spans(tiny_serve):
    """`trace_reduce.py` reads the trace of a program with spans: its idle
    gaps are still attributed to the harness's spans."""
    har = tiny_serve["harness"]
    assert har["busy_s"] == pytest.approx(0.000151428, rel=1e-9)
    assert har["idle_gaps"]
    assert all(n.startswith("bench.") or n in (
        span_reduce.tr.SHORT_GAP, "outside harness spans")
        for n, _ in har["idle_gaps"])
    assert har["idle_gaps"][0][0].startswith("bench.tick")


def test_tiny_serve_readers(tiny_serve):
    ctx = {"kind": "serve", "entry_jit": "_session_tick_jit",
           "trace": dict(tiny_serve["spans"])}
    read = lambda m: harness.load_module("metrics", m).read(ctx)  # noqa: E731
    assert read("outcome_ms_per_tick.serve") == pytest.approx(
        20.0600935, rel=1e-9)
    assert read("admit_ms_per_tick.serve") == pytest.approx(
        12.2645695, rel=1e-9)
    assert read("pack_ms_per_tick.serve") == pytest.approx(
        1.0060395, rel=1e-9)
    assert read("eager_ops_per_tick.serve") == 78.0
