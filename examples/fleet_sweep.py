"""Fleet walkthrough: one launchable co-design DSE job, three ways.

    PYTHONPATH=src python examples/fleet_sweep.py

Runs the same small (chiplets x placements x workloads) grid through
`python -m repro.launch.fleet`:

  1. a fresh process with the persistent cache off (cold compiles),
  2. the same job in new processes sharing the persistent cache
     ($JAX_COMPILATION_CACHE_DIR, else .jax_cache in the checkout): the
     second of them starts warm — what a fleet worker joining
     mid-campaign experiences,
  3. one emulated-host shard (`--shard 0:2`): the contiguous grid rows a
     real 2-process fleet member would own, bit-identical to rows 0..k/2
     of the full run.

On a multi-host deployment the same job runs as one worker per host:

    python -m repro.launch.fleet --processes 8 --process-id $RANK \
        --coordinator head-node:12345

with JAX_COMPILATION_CACHE_DIR pointing every worker at one shared cache.
"""
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

GRID = ["--chiplets", "4,9", "--placements", "2",
        "--workloads", "uniform,bursty", "--intervals", "8",
        "--reps", "2", "--seed", "0"]


def fleet(extra, out_path):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    subprocess.run(
        [sys.executable, "-m", "repro.launch.fleet", *GRID, *extra,
         "--out", str(out_path)],
        cwd=REPO, env=env, check=True)
    with open(out_path) as f:
        return json.load(f)


def main():
    with tempfile.TemporaryDirectory() as tmp:
        tmp = Path(tmp)

        print("== 1. cold run (persistent cache off) ==")
        cold = fleet(["--no-cache"], tmp / "cold.json")
        print(f"   {cold['grid_points']} grid points, first call "
              f"{cold['first_call_s']:.2f}s (compiles), then "
              f"{cold['points_per_sec']:.1f} points/s; best point "
              f"{cold['best_point']['label']}")

        print("== 2. warm run (new process after one that filled the "
              "cache) ==")
        fleet([], tmp / "fill.json")
        warm = fleet([], tmp / "warm.json")
        print(f"   first call {warm['first_call_s']:.2f}s — "
              f"{warm['first_call_s'] / cold['first_call_s']:.0%} of cold "
              f"({warm['cache']['entries']} cache entries, "
              f"{warm['cache']['bytes'] / 1e6:.1f} MB)")

        print("== 3. emulated-host shard 0 of 2 ==")
        shard = fleet(["--shard", "0:2"], tmp / "shard.json")
        print(f"   {shard['grid_points']} of "
              f"{shard['grid_points_full']} points "
              f"({shard['sweep_wall_s']:.3f}s) — the same rows a real "
              f"2-process fleet member owns")


if __name__ == "__main__":
    main()
