"""The harness's lookup by name, its work count, its refusal to run
without a TPU, and the arithmetic of the trace reduction."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import work_count  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_finds_its_files_by_name(cell):
    spec = harness.load_cell(cell)
    assert spec["cell"]["name"] == cell
    assert spec["config"]["arch"] in spec["reference"].ARCHS
    assert hasattr(harness.load_module("runners", spec["mix"]["runner"]),
                   "Runner")
    assert spec["limits"]["summary_gap"] > 0
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    BENCHMARK["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    read = harness.load_module("metrics", metric).read
    assert read({"kind": "none", "trace": {"window_s": 0.0}}) is None


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        harness.load_cell("no.such-cell")


@pytest.mark.parametrize("kind", ["runners", "references", "metrics"])
def test_unknown_module_name_is_refused(kind):
    with pytest.raises(FileNotFoundError):
        harness.load_module(kind, "no_such_module")


def test_reference_refuses_an_arch_it_does_not_model():
    ref = harness.load_module("references", "resipi")
    ref.check_config({"arch": "resipi"})
    with pytest.raises(ValueError):
        ref.check_config({"arch": "prowaves"})


def test_grid_product_crosses_in_key_order():
    grid = harness.load_module("runners", "sweep").build_grid({"combine": "product", "grid": {
        "l_m": {"linspace": [0.004, 0.032, 32]}, "wavelengths": [2, 4, 8,
                                                                 16]}})
    assert len(grid["l_m"]) == 128
    assert list(grid["wavelengths"][:4]) == [2, 4, 8, 16]
    assert float(grid["l_m"][4]) == pytest.approx(0.004 + 0.028 / 31)


def test_serve_pool_holds_the_same_work_for_every_seed():
    serve = harness.load_module("runners", "serve")
    block = serve.priority_block([0.5, 0.35, 0.15], 20)
    assert list(block) == [0] * 10 + [1] * 7 + [2] * 3
    with pytest.raises(ValueError):
        serve.priority_block([0.5, 0.5], 3)
    draws = [serve.stratified(np.random.default_rng(s), np.arange(51), 510)
             for s in (1, 2)]
    assert sorted(draws[0]) == sorted(draws[1])
    assert list(draws[0]) != list(draws[1])
    assert sorted(draws[0][:51]) == list(range(51))


def test_seed_words_take_large_seeds():
    a = harness.seed_key_words(2 ** 40 + 3)
    b = harness.seed_key_words(2 ** 40 + 4)
    assert a.dtype.name == "uint32" and a.shape == (2,)
    assert tuple(a) != tuple(b)
    assert tuple(a) == tuple(harness.seed_key_words(2 ** 40 + 3))


def test_work_count_at_a_known_shape():
    w = work_count.interval_work(traces=1, trace_chiplets=2, intervals=10,
                                 chiplets_per_lane=[2, 1], gateways=4,
                                 memory_gateways=2)
    read = 1 * 10 * (2 * 2 + 2) * 4
    written = 2 * 10 * (6 * 4 + 1) + 3 * 10 * 3 * 4 + 2 * 8 * 4
    assert w["bytes"] == read + written
    slots = (2 * 4 + 2) + (1 * 4 + 2)
    assert w["ops"] == 10 * (3 * 60 + slots * 8 + 2 * 50)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    least = work_count.least_seconds(w, peaks)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(w["bytes"] / 1e9)
    assert work_count.least_seconds(w, peaks, 4)["seconds"] \
        == pytest.approx(w["bytes"] / 4e9)


def test_peaks_table_knows_the_v5e_and_refuses_others():
    assert trace_reduce.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        trace_reduce.peaks_for("TPU v9 imaginary")


def test_union_self_time_and_instruction_names():
    assert trace_reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce.covered([(0, 2), (1, 3), (5, 6)]) == 4
    st = trace_reduce.self_times([("%while.1 = w", 0, 10),
                                  ("%fusion.2 = f", 2, 5),
                                  ("%fusion.2 = f", 6, 7)])
    assert st["%while.1 = w"] == 6 and st["%fusion.2 = f"] == 4
    assert trace_reduce.instr_name("%fusion.36 = f32[4] fusion(x)") \
        == "fusion.36"


@pytest.mark.parametrize("script", ["run.py", "control.py"])
def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, f"perfbench/{script}", "--workload",
         BENCHMARK["workloads"][0]["name"],
         *(["--seed", "7", "--trace", "0"] if script == "run.py"
           else ["--seeds", "7"]),
         "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
