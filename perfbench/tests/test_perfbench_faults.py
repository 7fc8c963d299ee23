"""A sound run reads correct; the control and each fault the cells can
have read not correct. Each case is a small run on the CPU in a process
of its own (perfbench/tests/case_runner.py), the chip check skipped."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

RUNNER = Path(__file__).resolve().parent / "case_runner.py"
SEED = 31415926535


def run_case(kind, fault, cache):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(RUNNER), kind, fault, str(SEED), str(cache)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("perfbench_jax_cache")


@pytest.mark.parametrize("kind", ["sweep", "topology", "serve"])
def test_sound_run_is_correct(kind, cache):
    res = run_case(kind, "none", cache)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    gap = res["checks"]["summary_gap"]
    assert gap["value"] < gap["limit"]


CASES = [(kind, fault) for kind in ("sweep", "serve")
         for fault in ("control", "state_unchanged", "half_batch",
                       "answer_altered")]


@pytest.mark.parametrize("kind,fault", CASES)
def test_fault_reads_not_correct(kind, fault, cache):
    res = run_case(kind, fault, cache)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] > 0
    gap = res["checks"]["summary_gap"]
    assert gap["value"] > gap["limit"]
