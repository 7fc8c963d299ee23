"""CPU verification gate: tier-1 pytest + a fast padded-sweep smoke.

`make verify` (or `python benchmarks/smoke.py`) is the pre-merge check:

  1. the repo's tier-1 test suite (ROADMAP.md) via pytest, and
  2. a ~5 s compiled padded-topology-sweep smoke that asserts the engine's
     two load-bearing invariants on CPU — the whole topology grid runs as
     ONE scan-body trace, and padded results match unpadded `simulate` —
     so regressions in the compiled padded path are caught without a TPU,
  3. the same pair of invariants for the gateway-placement axis
     (`sweep_placement`: K placements, one trace, unpadded parity),
  4. the workload/time axis: a mixed-length `sweep_workload` runs as one
     scan-body trace with T-padded lanes matching unpadded `simulate`,
     and a chunked `SimSession` bit-matches the one-shot records,
  5. the device-resident placement search: a whole annealed search is ONE
     scan-body trace and ONE dispatch, and its best score matches a fresh
     host-oracle `simulate` of the found placement (device/host parity),
  6. the Pareto co-design engine: a joint (topology x placement x knob)
     `search_codesign` is ONE dispatch, its front is mutually
     non-dominated, and a host-oracle re-score of every front entry
     reproduces the archived objectives at 1e-6,
  7. the fault-injection path: a fault frame masked at t == T matches the
     fault-free `simulate`, a firing fault reuses the same executable, and
     the fault grid vmaps as one more sweep axis (one scan-body trace),
  8. the session server: a short continuous-batching soak — nominal load
     drops zero healthy sessions on one shared executable, an overload
     burst sheds by policy with the queue staying bounded,
  9. the fused epoch_step kernel: `epoch_kernel=True` reproduces the scan
     body at 1e-6 through `simulate` — clean, destination-aware, and
     faulted — in interpret mode (the engine-parity gate off-TPU),
 10. the fleet: a REAL 2-process `jax.distributed` CPU mesh (gloo
     collectives, local coordinator) runs a small co-design grid through
     `python -m repro.launch.fleet` and must reproduce the single-process
     run per-point at 1e-6 (the GSPMD-sharded-executable parity gate).

`--smoke-only` skips the pytest stage (used by CI wrappers that already
ran the suite, and for quick local iteration).
"""
from __future__ import annotations

import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO / "src") not in sys.path:        # standalone-invocation bootstrap
    sys.path.insert(0, str(REPO / "src"))


def padded_sweep_smoke() -> None:
    import jax
    import numpy as np

    from repro.core import traffic
    from repro.core.constants import NETWORK
    from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                      reset_engine_stats, simulate,
                                      sweep_topology, topology_point_config)

    t0 = time.time()
    grid_c, grid_g = [4, 9, 16, 25], [4, 2, 4, 2]
    cfg = NETWORK.with_topology(n_chiplets=max(grid_c))
    tr = traffic.generate_trace("dedup", 16, jax.random.PRNGKey(0), cfg)
    base = SimConfig().with_arch(Arch.RESIPI)

    reset_engine_stats()
    out = sweep_topology(tr, base, n_chiplets=grid_c,
                         gateways_per_chiplet=grid_g)
    lat = np.asarray(out["summary"]["mean_latency"])
    traces = engine_stats()["simulate_traces"]
    assert lat.shape == (len(grid_c),) and np.all(np.isfinite(lat)), lat
    assert traces == 1, f"expected ONE scan-body trace, got {traces}"

    # padded-vs-unpadded parity on one mid-grid point
    c, g, i = grid_c[1], grid_g[1], 1
    ref = simulate(traffic.slice_trace(tr, c),
                   topology_point_config(base, n_chiplets=c,
                                         gateways_per_chiplet=g))["summary"]
    for k in ("mean_latency", "mean_power_mw", "mean_gateways"):
        np.testing.assert_allclose(
            np.asarray(out["summary"][k][i]), np.asarray(ref[k]),
            rtol=1e-4, atol=1e-4,
            err_msg=f"padded grid point (c={c}, g={g}) diverged on {k}")

    # warm re-call must not re-trace
    before = engine_stats()["simulate_traces"]
    sweep_topology(tr, base, n_chiplets=grid_c, gateways_per_chiplet=grid_g)
    assert engine_stats()["simulate_traces"] == before, "warm call re-traced"
    print(f"padded-sweep smoke OK in {time.time() - t0:.1f}s "
          f"({len(grid_c)} topologies, 1 trace, parity holds)")


def placement_sweep_smoke() -> None:
    """Compiled placement path: K placements, one trace, unpadded parity."""
    import dataclasses

    import jax
    import numpy as np

    from repro.core import traffic
    from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                      reset_engine_stats, simulate,
                                      sweep_placement)

    t0 = time.time()
    tr = traffic.generate_trace("dedup", 12, jax.random.PRNGKey(1))
    base = SimConfig().with_arch(Arch.RESIPI)
    center = ((1, 1), (2, 2), (1, 2), (2, 1))

    reset_engine_stats()
    out = sweep_placement(tr, base, [None, center])
    assert engine_stats()["simulate_traces"] == 1, "placement sweep re-traced"
    ref = simulate(tr, dataclasses.replace(
        base, cfg=base.cfg.with_placement(center)))["summary"]
    np.testing.assert_allclose(
        np.asarray(out["summary"]["mean_latency"][1]),
        np.asarray(ref["mean_latency"]), rtol=1e-6,
        err_msg="placement lane diverged from unpadded simulate")
    print(f"placement-sweep smoke OK in {time.time() - t0:.1f}s "
          f"(2 placements, 1 trace, parity holds)")


def traffic_stream_smoke() -> None:
    """Workload/time axis: T-padded parity + streaming-vs-oneshot match."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import traffic
    from repro.core.simulator import (Arch, SimConfig, SimSession,
                                      engine_stats, reset_engine_stats,
                                      simulate, sweep_workload)

    t0 = time.time()
    base = SimConfig().with_arch(Arch.RESIPI)
    specs = [traffic.ParsecSpec(app="dedup", n_intervals=10),
             traffic.UniformSpec(n_intervals=16),
             traffic.BurstySpec(n_intervals=12)]

    # mixed-length workload sweep: ONE scan-body trace, padded-lane parity
    reset_engine_stats()
    out = sweep_workload(specs, base, seed=0)
    traces = engine_stats()["simulate_traces"]
    assert traces == 1, f"expected ONE scan-body trace, got {traces}"
    keys = jax.random.split(jax.random.PRNGKey(0), len(specs))
    for i, (sp, ky) in enumerate(zip(specs, keys)):
        ref = simulate(traffic.generate(sp, ky), base)["summary"]
        np.testing.assert_allclose(
            np.asarray(out["summary"]["mean_latency"][i]),
            np.asarray(ref["mean_latency"]), rtol=1e-6,
            err_msg=f"padded workload lane {sp.name} diverged")

    # streaming session: chunked records bit-match the one-shot scan
    tr = traffic.generate_trace("canneal", 24, jax.random.PRNGKey(1))
    one = simulate(tr, base)
    session = SimSession.init(base)
    recs = [session.step_chunk(ch)["records"]
            for ch in traffic.chunk_trace(tr, 8)]
    cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *recs)
    for k in ("latency", "power_mw", "g"):
        assert np.array_equal(np.asarray(cat[k]),
                              np.asarray(one["records"][k])), \
            f"streamed records[{k}] diverged from one-shot simulate"
    np.testing.assert_allclose(
        np.asarray(session.summary()["mean_latency"]),
        np.asarray(one["summary"]["mean_latency"]), rtol=1e-6)
    print(f"traffic/streaming smoke OK in {time.time() - t0:.1f}s "
          f"({len(specs)} mixed-length workloads, 1 trace, chunked "
          f"records bit-match)")


def search_smoke() -> None:
    """Device-resident search: one trace + one dispatch + oracle parity."""
    import dataclasses

    import jax
    import numpy as np

    from repro.core import traffic
    from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                      reset_engine_stats, search_placement,
                                      simulate)

    t0 = time.time()
    tr = traffic.generate_trace("dedup", 12, jax.random.PRNGKey(2))
    base = SimConfig().with_arch(Arch.RESIPI)

    reset_engine_stats()
    res = search_placement(tr, base, generations=4, population=6, seed=0)
    stats = engine_stats()
    assert stats["simulate_traces"] <= 1, \
        f"search re-traced per generation: {stats}"
    assert stats["search_dispatches"] == 1, \
        f"search was not ONE dispatch: {stats}"
    assert res["best_score"] <= res["default_score"]

    # Host-oracle parity: re-score the found placement through unpadded
    # simulate (numpy tables) — must match the device path's traced tables.
    # (This traces its own single-config executable, so the warm-search
    # accounting below starts from a fresh reset.)
    single = simulate(tr, dataclasses.replace(
        base, cfg=base.cfg.with_placement(res["best_placement"])))
    ref = float(np.mean(np.asarray(single["records"]["mean_inter_latency"])))
    np.testing.assert_allclose(
        res["best_score"], ref, rtol=1e-5,
        err_msg="device search score diverged from the host oracle")

    # Warm re-seeded search: zero new traces, exactly one dispatch.
    reset_engine_stats()
    search_placement(tr, base, generations=4, population=6, seed=3)
    stats2 = engine_stats()
    assert stats2["simulate_traces"] == 0, "warm search re-traced"
    assert stats2["search_dispatches"] == 1
    print(f"search smoke OK in {time.time() - t0:.1f}s "
          f"(4x6 annealed search, 1 dispatch, oracle parity holds)")


def pareto_smoke() -> None:
    """Pareto co-design: the joint (topology x placement x knob) search is
    ONE dispatch, the returned front is mutually non-dominated, and a
    host-oracle re-score of every front entry reproduces its archived
    objectives at 1e-6 (the device/host co-design parity gate)."""
    import jax
    import numpy as np

    from repro.core import pareto, traffic
    from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                      reset_engine_stats)

    t0 = time.time()
    base = SimConfig().with_arch(Arch.RESIPI)
    grid_c = [9, 16]
    cfg = base.cfg.with_topology(n_chiplets=max(grid_c))
    traces = [traffic.generate_trace(a, 8, jax.random.PRNGKey(i), cfg)
              for i, a in enumerate(["dedup", "streamcluster"])]

    reset_engine_stats()
    res = pareto.search_codesign(traces, base, n_chiplets=grid_c,
                                 islands=2, generations=4, population=4,
                                 archive=16, seed=0)
    stats = engine_stats()
    assert stats["search_dispatches"] == 1, \
        f"co-design search was not ONE dispatch: {stats}"
    assert stats["simulate_traces"] <= 1, \
        f"co-design search re-traced the scan body: {stats}"
    assert res["front"], "co-design search returned an empty front"

    # The front is mutually non-dominated.
    obj = np.asarray([[e["objectives"][k] for k in
                       ("latency", "power_mw", "energy")]
                      for e in res["front"]])
    le = (obj[:, None] <= obj[None, :]).all(-1)
    lt = (obj[:, None] < obj[None, :]).any(-1)
    dominated = (le & lt).any(axis=0)
    assert not dominated.any(), "device front contains a dominated point"

    # Host-oracle parity: unpadded re-simulation of every front entry.
    rescored = pareto.rescore_front_host(res, traces, base)
    np.testing.assert_allclose(rescored, obj, rtol=1e-6, atol=1e-9,
                               err_msg="device front diverged from the "
                                       "host-oracle re-score")
    print(f"pareto smoke OK in {time.time() - t0:.1f}s "
          f"({len(grid_c)} topologies x 2 islands, 1 dispatch, "
          f"{len(res['front'])}-point front, oracle parity holds)")


def fault_smoke() -> None:
    """Compiled fault path: one trace per entry point + never-fire parity.

    The parity half is the fault-masking contract on CPU: a fault frame
    whose window starts at t == T (so it never fires inside the simulated
    horizon) must match the fault-free `simulate` — same executable
    discipline as the padded-lane invariants above.
    """
    import jax
    import numpy as np

    from repro.core import faults, traffic
    from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                      reset_engine_stats, simulate,
                                      sweep_faults)

    t0 = time.time()
    base = SimConfig().with_arch(Arch.RESIPI)
    T = 16
    tr = traffic.generate_trace("dedup", T, jax.random.PRNGKey(3))
    clean = simulate(tr, base)["summary"]

    # Fault masked at t == T: in-window never fires -> fault-free parity.
    masked = faults.compile_faults(
        [faults.GatewayFault(start=T, chiplet=0, slot=0),
         faults.LossDrift(start=T, db_per_interval=0.5)], base.cfg, T)
    reset_engine_stats()
    out = simulate(faults.attach_faults(tr, masked), base)["summary"]
    assert engine_stats()["simulate_traces"] == 1
    for k in ("mean_latency", "mean_power_mw", "mean_energy"):
        np.testing.assert_allclose(
            np.asarray(out[k]), np.asarray(clean[k]), rtol=1e-6,
            err_msg=f"never-firing fault frame diverged from fault-free "
                    f"simulate on {k}")

    # A firing fault reuses the same executable and moves the result.
    firing = faults.compile_faults(
        [faults.GatewayFault(start=2, chiplet=0, slot=0)], base.cfg, T)
    before = engine_stats()["simulate_traces"]
    hurt = simulate(faults.attach_faults(tr, firing), base)["summary"]
    assert engine_stats()["simulate_traces"] == before, \
        "a different fault pattern re-traced the fault path"
    assert float(hurt["mean_gateways"]) < float(clean["mean_gateways"]), \
        "hard gateway failure did not reduce effective gateways"

    # The fault grid is one more vmapped axis: K frames, one new trace.
    reset_engine_stats()
    sw = sweep_faults(tr, base, [masked, firing])
    assert engine_stats()["simulate_traces"] == 1
    lat = np.asarray(sw["summary"]["mean_latency"])
    np.testing.assert_allclose(lat[0], np.asarray(clean["mean_latency"]),
                               rtol=1e-6)
    print(f"fault smoke OK in {time.time() - t0:.1f}s "
          f"(t==T parity, 1 trace per entry point, fault grid vmaps)")


def serve_soak_smoke() -> None:
    """Session-server soak: shared executable, zero healthy drops at
    nominal load, nonzero policy shed under an overload burst."""
    import jax
    import numpy as np

    from repro.core import traffic
    from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                      reset_engine_stats)
    from repro.serve.engine import SessionServer, replay_standalone
    from repro.serve.policies import ServerPolicy
    from repro.serve.scheduler import SessionRequest

    t0 = time.time()
    base = SimConfig().with_arch(Arch.RESIPI)

    # Nominal: a mixed-length mix well inside capacity — every admitted
    # session completes, the whole run is ONE scan-body trace, and a
    # sampled session bit-matches its standalone replay.
    server = SessionServer(base, ServerPolicy(lanes=3, chunk_intervals=6,
                                              queue_capacity=8))
    reset_engine_stats()
    for i in range(5):
        tr = traffic.generate_trace("dedup", 5 + 3 * i, jax.random.PRNGKey(i))
        server.submit(SessionRequest(trace=tr, priority=i % 3))
    server.drain()
    traces = engine_stats()["simulate_traces"]
    assert traces <= 1, f"serve soak re-traced per tick: {traces}"
    m = server.metrics()
    assert m["completed"] == m["admitted"] == 5, \
        f"nominal load dropped healthy sessions: {m}"
    sess = server.completed[0]
    ref = replay_standalone(base, sess)
    for k in ("mean_latency", "mean_energy", "valid_intervals"):
        assert float(ref[k]) == sess.summary()[k], \
            f"packed lane diverged from standalone replay on {k}"

    # Overload: a burst over queue capacity sheds by policy and the queue
    # never grows past its bound.
    over = SessionServer(base, ServerPolicy(lanes=2, chunk_intervals=6,
                                            queue_capacity=3))
    for i in range(10):
        tr = traffic.generate_trace("canneal", 12, jax.random.PRNGKey(i))
        over.submit(SessionRequest(trace=tr))
    over.drain()
    mo = over.metrics()
    shed = mo["shed_queue_full"] + mo["shed_memory"] + mo["shed_priority"]
    assert shed > 0, f"overload burst shed nothing: {mo}"
    depths = [e["queue_depth"] for e in over.events]
    assert max(depths) <= 3, f"queue grew past capacity: {max(depths)}"
    assert mo["completed"] == mo["admitted"], \
        f"overload dropped admitted sessions: {mo}"
    assert np.isfinite([s.summary()["mean_latency"]
                        for s in over.sessions.values()]).all()
    print(f"serve soak smoke OK in {time.time() - t0:.1f}s "
          f"(1 trace, 0 healthy drops, {shed} shed under overload, "
          f"replay parity holds)")


def kernel_parity_smoke() -> None:
    """Fused epoch_step kernel vs the lax.scan body through `simulate`:
    summaries agree at 1e-6 on the clean, destination-aware, and faulted
    paths (interpret mode — the off-TPU engine-parity gate)."""
    import dataclasses

    import jax
    import numpy as np

    from repro.core import traffic
    from repro.core.faults import GatewayFault, attach_faults, compile_faults
    from repro.core.simulator import SUMMARY_KEYS, SimConfig, simulate

    t0 = time.time()
    sim = SimConfig()
    sim_k = dataclasses.replace(sim, epoch_kernel=True)
    clean = traffic.generate(traffic.UniformSpec(n_intervals=24),
                             jax.random.PRNGKey(0))
    dest = traffic.generate(
        traffic.PermutationSpec(pattern="transpose", mean_load=0.05,
                                n_intervals=24),
        jax.random.PRNGKey(1), dest=True)
    frame = compile_faults((GatewayFault(chiplet=0, slot=0, start=4),),
                           sim.cfg, 24, seed=3)
    for name, tr in (("clean", clean), ("dest", dest),
                     ("faults", attach_faults(dict(clean), frame))):
        a, b = simulate(tr, sim_k), simulate(tr, sim)
        for k in SUMMARY_KEYS:
            np.testing.assert_allclose(
                np.asarray(a["summary"][k]), np.asarray(b["summary"][k]),
                rtol=1e-6, atol=1e-6,
                err_msg=f"kernel parity broke: {name} summary[{k}]")
    print(f"epoch_step kernel parity smoke OK in {time.time() - t0:.1f}s "
          f"(clean/dest/faulted summaries match the scan body at 1e-6)")


def distributed_smoke() -> None:
    """Real 2-process jax.distributed fleet vs the single-process run:
    the same small co-design grid, per-point parity at 1e-6."""
    import json
    import os
    import tempfile

    t0 = time.time()
    grid = ["--chiplets", "4,9", "--placements", "2",
            "--workloads", "uniform,bursty", "--intervals", "6",
            "--seed", "0", "--dump-points"]
    # The children run on the CPU backend: this process already holds the
    # backend it started with, and an accelerator serves one process.
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    with tempfile.TemporaryDirectory(prefix="fleet-smoke-") as td:
        outs = {}
        for tag, extra in (("single", ["--shard", "0:1"]),
                           ("dist", ["--processes", "2"])):
            out = Path(td) / f"{tag}.json"
            cmd = [sys.executable, "-m", "repro.launch.fleet",
                   "--out", str(out)] + grid + extra
            proc = subprocess.run(cmd, cwd=REPO, env=env, timeout=600,
                                  capture_output=True, text=True)
            assert proc.returncode == 0, \
                f"fleet {tag} run failed:\n{proc.stdout}\n{proc.stderr}"
            outs[tag] = json.loads(out.read_text())
    single, dist = outs["single"], outs["dist"]
    assert dist["process_count"] == 2 and dist["device_count"] >= 2, dist
    assert single["labels"] == dist["labels"]
    for lbl, a, b in zip(single["labels"], single["mean_latency"],
                         dist["mean_latency"]):
        assert abs(a - b) <= 1e-6 * max(abs(a), 1.0), \
            f"fleet point {lbl} diverged: single {a} vs 2-process {b}"
    print(f"distributed smoke OK in {time.time() - t0:.1f}s "
          f"({single['grid_points']} grid points, 2-process gloo mesh, "
          f"per-point parity holds)")


def main(argv) -> int:
    if "--smoke-only" not in argv:
        rc = subprocess.call(
            [sys.executable, "-m", "pytest", "-x", "-q"], cwd=REPO)
        if rc != 0:
            print("tier-1 pytest FAILED", file=sys.stderr)
            return rc
    padded_sweep_smoke()
    placement_sweep_smoke()
    traffic_stream_smoke()
    search_smoke()
    pareto_smoke()
    fault_smoke()
    serve_soak_smoke()
    kernel_parity_smoke()
    distributed_smoke()
    print("verify OK")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
