"""The trace-to-metric reduction on a small profiler trace recorded on a
TPU v5e: two calls of a 2-trace x 2-point `sweep_batch` under the
harness's annotations (`bench.window`, `bench.tracegen`, `bench.entry`,
`bench.block`)."""
import gzip
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "tiny_sweep.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(DATA) as src, open(d / "tpu.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace_reduce.reduce_dir(d.parents[2],
                                   {"entry_jit": "_sweep_batch_jit"})


def test_window_is_the_bench_window_span(reduced):
    assert reduced["window_s"] == pytest.approx(0.032205303, rel=1e-9)


def test_busy_is_the_union_of_device_ops(reduced):
    assert len(reduced["devices"]) == 1
    assert reduced["busy_s"] == pytest.approx(152862e-9, rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_scan_time_is_the_entry_loop(reduced):
    # Two calls, each one `%while` loop inside jit__sweep_batch_jit.
    assert reduced["scan_s"] == [pytest.approx(94625e-9, rel=1e-9)]
    assert reduced["scan_s"][0] < reduced["busy_s"]


def test_no_collectives_on_one_chip(reduced):
    assert reduced["n_collectives"] == [0]
    assert reduced["collective_s"] == [0.0]


def test_device_ops_and_idle_gaps_are_named(reduced):
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= trace_reduce.TOP
    assert all(isinstance(n, str) and v > 0 for n, v in ops)
    assert any(n.startswith("jit__sweep_batch_jit/") for n, _ in ops)
    gaps = reduced["idle_gaps"]
    assert all(n.split(":")[0].startswith("bench.")
               or n == trace_reduce.SHORT_GAP for n, _ in gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(v for _, v in gaps) <= idle * (1 + 1e-9)
