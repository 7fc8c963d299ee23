"""Fleet-layer tests: partitioning math, GridSharding invariants, the
sharded sweep surfaces, and the fleet launcher's deterministic grid.

The multi-DEVICE compiled path (pad + NamedSharding + gather parity) runs
in a subprocess with a forced 4-device host platform — XLA_FLAGS must be
set before jax initializes, which the in-process suite cannot do. The
multi-PROCESS path (real jax.distributed + gloo) is covered by
`benchmarks/smoke.py::distributed_smoke` (make verify) and
benchmarks/bench_distributed.py.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import traffic
from repro.core.distributed import (GridSharding, init_distributed,
                                    is_distributed, partition_bounds)
from repro.core.simulator import (Arch, SimConfig, shard_sweep,
                                  sweep_workload)
from repro.launch import fleet

REPO = Path(__file__).resolve().parent.parent


def _sim() -> SimConfig:
    return SimConfig().with_arch(Arch.RESIPI)


# ---------------------------------------------------------------------------
# partition_bounds: the emulated-host contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 5, 8, 13, 64])
@pytest.mark.parametrize("n", [1, 2, 3, 4, 7])
def test_partition_bounds_disjoint_cover(k, n):
    covered = []
    for i in range(n):
        start, stop = partition_bounds(k, n, i)
        assert 0 <= start <= stop <= k
        covered.extend(range(start, stop))
    assert covered == list(range(k))


def test_partition_bounds_matches_padded_block_layout():
    # 13 points on 4 shards pad to 16 -> blocks of 4; the pad lands in the
    # last block (exactly how a 1-D NamedSharding lays out the padded axis).
    assert [partition_bounds(13, 4, i) for i in range(4)] == \
        [(0, 4), (4, 8), (8, 12), (12, 13)]


def test_partition_bounds_rejects_out_of_range_shard():
    with pytest.raises(ValueError):
        partition_bounds(8, 2, 2)


# ---------------------------------------------------------------------------
# init_distributed: single-process fallback
# ---------------------------------------------------------------------------

def test_init_distributed_single_process_is_noop_and_idempotent():
    info = init_distributed()
    assert info["distributed"] is False
    assert info["num_processes"] == 1 and info["process_id"] == 0
    assert not is_distributed()
    assert init_distributed() == info      # second call: same answer


# ---------------------------------------------------------------------------
# GridSharding: single-device passthrough invariants
# ---------------------------------------------------------------------------

def test_grid_sharding_single_device_is_passthrough():
    gs = GridSharding(5)
    assert gs.describe() == {"grid_points": 5, "pad_lanes": 0,
                             "devices": 1, "processes": 1}
    x = np.arange(10.0).reshape(5, 2)
    sharded = gs.shard(x)
    np.testing.assert_array_equal(np.asarray(sharded), x)
    # replicate is IDENTITY on single-process meshes (the warm-cache
    # behaviour every existing test pins must not change)
    obj = {"a": x, "b": None}
    assert gs.replicate(obj) is obj
    np.testing.assert_array_equal(np.asarray(gs.gather(sharded)), x)


def test_grid_sharding_rejects_empty_devices():
    with pytest.raises(ValueError):
        GridSharding(4, devices=[])


def test_grid_sharding_pad_tree_repeats_last_row():
    gs = GridSharding(3)
    gs.pad = 2                     # exercise the pad path on one device
    x = np.arange(6.0).reshape(3, 2)
    padded = np.asarray(gs.pad_tree(x))
    assert padded.shape == (5, 2)
    np.testing.assert_array_equal(padded[3], x[-1])
    np.testing.assert_array_equal(padded[4], x[-1])
    # gather slices the pad back off
    np.testing.assert_array_equal(np.asarray(gs.gather(padded)), x)


# ---------------------------------------------------------------------------
# Sharded sweep surfaces (single-device: metadata + unchanged numerics)
# ---------------------------------------------------------------------------

def test_shard_sweep_reports_sharding_metadata():
    sim = _sim()
    tr = traffic.generate(traffic.UniformSpec(n_intervals=6),
                          jax.random.PRNGKey(0),
                          sim.cfg.with_topology(n_chiplets=9))
    out = shard_sweep([tr], sim, n_chiplets=[4, 9])
    assert out["summary"]["pad_lanes"] == 0
    assert out["sharding"] == {"grid_points": 2, "pad_lanes": 0,
                               "devices": 1, "processes": 1}


def test_sweep_workload_devices_none_is_unchanged():
    sim = _sim()
    specs = [traffic.UniformSpec(n_intervals=6),
             traffic.BurstySpec(n_intervals=6)]
    a = sweep_workload(specs, sim, n_chiplets=[4, 9])
    b = sweep_workload(specs, sim, n_chiplets=[4, 9], devices=None)
    np.testing.assert_array_equal(
        np.asarray(a["summary"]["mean_latency"]),
        np.asarray(b["summary"]["mean_latency"]))
    assert "sharding" not in a


def test_sweep_workload_gen_chiplets_validation():
    sim = _sim()
    specs = [traffic.UniformSpec(n_intervals=6)]
    with pytest.raises(ValueError, match="gen_chiplets"):
        sweep_workload(specs, sim, n_chiplets=[16], gen_chiplets=9)


def test_sweep_workload_gen_chiplets_pins_trace_generation():
    # A shard whose slice misses the global max chiplet count still
    # reproduces the full run's rows when gen_chiplets + keys are pinned.
    sim = _sim()
    specs = [traffic.UniformSpec(n_intervals=6),
             traffic.BurstySpec(n_intervals=6),
             traffic.UniformSpec(n_intervals=6),
             traffic.BurstySpec(n_intervals=6)]
    cs = [4, 4, 16, 16]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    full = sweep_workload(specs, sim, keys=keys, n_chiplets=cs)
    half = sweep_workload(specs[:2], sim, keys=keys[:2], n_chiplets=cs[:2],
                          gen_chiplets=16)
    np.testing.assert_allclose(
        np.asarray(half["summary"]["mean_latency"]),
        np.asarray(full["summary"]["mean_latency"])[:2], rtol=1e-6)


# ---------------------------------------------------------------------------
# Fleet launcher: deterministic grid construction
# ---------------------------------------------------------------------------

def test_fleet_grid_is_deterministic_and_complete():
    cfg = _sim().cfg
    a = fleet.build_grid(cfg, chiplets=[4, 9], placements=3,
                         workloads=["uniform", "bursty"], intervals=6,
                         seed=7)
    b = fleet.build_grid(cfg, chiplets=[4, 9], placements=3,
                         workloads=["uniform", "bursty"], intervals=6,
                         seed=7)
    assert a["k"] == 2 * 3 * 2
    assert a["labels"] == b["labels"]
    assert a["grids"]["gateway_positions"] == b["grids"]["gateway_positions"]
    c = fleet.build_grid(cfg, chiplets=[4, 9], placements=3,
                         workloads=["uniform", "bursty"], intervals=6,
                         seed=8)
    assert a["grids"]["gateway_positions"] != c["grids"]["gateway_positions"]


def test_fleet_sample_placements_on_border():
    cfg = _sim().cfg
    ps = fleet.sample_placements(cfg, 4, seed=0)
    assert len(ps) == 4 and ps[0] is None
    r = cfg.mesh_x
    for p in ps[1:]:
        assert len(p) == cfg.max_gateways_per_chiplet
        assert len(set(p)) == len(p)
        for (x, y) in p:
            assert x in (0, r - 1) or y in (0, r - 1)


def test_fleet_slice_grid_concatenates_to_full():
    cfg = _sim().cfg
    grid = fleet.build_grid(cfg, chiplets=[4, 9], placements=2,
                            workloads=["uniform"], intervals=6, seed=0)
    parts = [fleet.slice_grid(grid, *partition_bounds(grid["k"], 3, i))
             for i in range(3)]
    assert sum(p["k"] for p in parts) == grid["k"]
    assert [l for p in parts for l in p["labels"]] == grid["labels"]


# ---------------------------------------------------------------------------
# Multi-device compiled path (forced 4-device host platform, subprocess)
# ---------------------------------------------------------------------------

_SHARDED_CHILD = r"""
import json, sys
import jax, numpy as np
from repro.core import traffic
from repro.core.simulator import Arch, SimConfig, sweep_workload
assert len(jax.devices()) == 4
sim = SimConfig().with_arch(Arch.RESIPI)
specs = [traffic.UniformSpec(n_intervals=6),
         traffic.BurstySpec(n_intervals=6),
         traffic.UniformSpec(n_intervals=6)]
import warnings
with warnings.catch_warnings():
    warnings.simplefilter("error")    # a warning on the sharded path fails
    a = sweep_workload(specs, sim, n_chiplets=[4, 9, 16],
                       devices=jax.devices())
b = sweep_workload(specs, sim, n_chiplets=[4, 9, 16])
la = np.asarray(a["summary"]["mean_latency"], np.float64)
lb = np.asarray(b["summary"]["mean_latency"], np.float64)
print("RESULT " + json.dumps({
    "parity": bool(np.allclose(la, lb, atol=1e-6)),
    "shape_ok": la.shape == (3,),
    "pad_lanes": int(a["summary"]["pad_lanes"]),
    "sharding": a["sharding"]}))
"""


def test_sharded_sweep_multi_device_parity_and_pad():
    env = dict(os.environ,
               PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4")
               .strip())
    proc = subprocess.run([sys.executable, "-c", _SHARDED_CHILD], cwd=REPO,
                          env=env, timeout=600, capture_output=True,
                          text=True)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    r = json.loads(line[len("RESULT "):])
    assert r["parity"] and r["shape_ok"]
    # 3 grid points on 4 devices: ONE padded lane, reported, never silent
    assert r["pad_lanes"] == 1
    assert r["sharding"]["devices"] == 4 and r["sharding"]["processes"] == 1
