"""Chip smoke test: the simulator's main path, end to end, on a TPU.

    python chip_smoke.py               # one chip, phases (a)-(e)
    python chip_smoke.py --four-chips  # phase (b)'s grid and (c)'s search,
                                       # sharded over 4 chips, then on 1

One process drives every phase through the public entry points, at the
sizes users run, and checks each result against the repository's own
reference for it:

  (a) paper anchor: the Table 1 system runs the 8 PARSEC profiles on all
      4 architectures through `simulate_batch` (fig11_main's 100
      intervals); RESIPI and RESIPI_ALL run again through the fused
      `epoch_step` kernel over 300 intervals (three grid steps), which
      must match the scan body at the tests' 1e-6;
  (b) scale: `sweep_topology_batch` of the 8 PARSEC traces over 16..256
      chiplets (checked against unpadded `simulate` at two points), and a
      256-chiplet kernel `simulate` with a destination matrix and fault
      frames over 300 intervals;
  (c) co-design: `search_codesign` at 64/144/256 chiplets, 8 workloads,
      4 islands (the demo frontier), its front re-scored on the host path;
  (d) serve: `SessionServer` under the `repro.launch.serve` mix with a
      fault storm and healing; every completed session must match its
      standalone replay at 1e-6 (bit-exact sessions are counted; the
      replay steps a donated session carry, the heal runs the donated
      device search);
  (e) residency: the `noc_step` kernel over 4096 cycles (16 grid steps)
      through `simulate_residency`, against its lax.scan reference.

Each phase prints its wall time and compile time. The script fails before
phase (a) when JAX finds no TPU, and any failed check exits non-zero.
The last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

TOL = dict(rtol=1e-6, atol=1e-6)       # the kernel/sharding parity tests'
PAPER_FIG11 = {"mean_latency": 0.37, "mean_power_mw": 0.25,
               "mean_energy": 0.53}    # ReSiPI vs PROWAVES, paper Fig. 11
ANCHOR_INTERVALS = 100                 # fig11_main's trace length
KERNEL_INTERVALS = 300                 # three 128-interval kernel grid steps
TOPOLOGY_GRID = [16, 36, 64, 100, 144, 196, 256]   # the demo's scan
CODESIGN_GRID = [64, 144, 256]         # the demo's frontier
RESIDENCY_CYCLES = 4096                # 16 noc_step grid steps of 256


class CompileClock:
    """Sums the backend compile durations JAX reports (XLA and Mosaic,
    persistent-cache lookups included) and counts the compilations."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def run_phase(clock, name, fn):
    s0, c0, t0 = clock.seconds, clock.count, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    print(f"[{name}] wall_s={wall:.3f} compile_s={clock.seconds - s0:.3f} "
          f"compiles={clock.count - c0}", flush=True)
    return out


def max_rel_diff(a, b):
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-12)))


def check_close(what, got, want, **tol):
    """np.testing.assert_allclose at `tol`, after a finite check."""
    import numpy as np
    got = np.asarray(got, np.float64)
    assert np.all(np.isfinite(got)), f"{what}: non-finite values"
    np.testing.assert_allclose(got, np.asarray(want, np.float64),
                               err_msg=what, **(tol or TOL))


def compiled_with_kernel(jax, fn, *args):
    """AOT-compile `fn` at `args`, require the Pallas kernel in it, and
    return the executable (the caller runs exactly what was inspected)."""
    exe = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text(), \
        "the compiled program holds no Pallas kernel (tpu_custom_call)"
    return exe


def array_leaves(trace):
    """The trace dict without its metadata (app names), for jit."""
    return {k: v for k, v in trace.items() if hasattr(v, "shape")}


def summary_diff(kernel, scan, keys):
    return max(max_rel_diff(kernel[k], scan[k]) for k in keys)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_anchor(jax, np):
    from repro.core import traffic
    from repro.core.simulator import (SUMMARY_KEYS, Arch, SimConfig,
                                      simulate_batch, stack_traces)

    base = SimConfig()                       # the paper's Table 1 system
    key = jax.random.PRNGKey(1)              # fig11_main's traces
    traces = [traffic.generate_trace(a, ANCHOR_INTERVALS, key)
              for a in traffic.APP_NAMES]
    summ = {}
    for arch in Arch:
        out = simulate_batch(traces, base.with_arch(arch))["summary"]
        summ[arch] = {k: np.asarray(out[k], np.float64) for k in SUMMARY_KEYS}
        for k, v in summ[arch].items():
            assert v.shape == (len(traces),) and np.all(np.isfinite(v)), \
                (arch, k, v)
    deltas = {m: float(np.mean(1.0 - summ[Arch.RESIPI][m]
                               / summ[Arch.PROWAVES][m]))
              for m in PAPER_FIG11}
    print("[a] fig11 ReSiPI vs PROWAVES: " + ", ".join(
        f"{m} -{100 * deltas[m]:.1f}% (paper -{100 * PAPER_FIG11[m]:.0f}%)"
        for m in PAPER_FIG11), flush=True)

    # The fused kernel, over more than one 128-interval grid step.
    long_traces = array_leaves(stack_traces(
        [traffic.generate_trace(a, KERNEL_INTERVALS, k) for a, k in zip(
            traffic.APP_NAMES,
            jax.random.split(jax.random.PRNGKey(2), len(traffic.APP_NAMES)))]))
    worst = 0.0
    for arch in (Arch.RESIPI, Arch.RESIPI_ALL):
        sim = base.with_arch(arch)
        sim_k = dataclasses.replace(sim, epoch_kernel=True)
        exe = compiled_with_kernel(
            jax, lambda b, s=sim_k: simulate_batch(b, s)["summary"],
            long_traces)
        kern = exe(long_traces)
        scan = simulate_batch(long_traces, sim)["summary"]
        worst = max(worst, summary_diff(kern, scan, SUMMARY_KEYS))
        for k in SUMMARY_KEYS:
            check_close(f"{arch.value} kernel vs scan summary[{k}]",
                        kern[k], scan[k])
    print(f"[a] epoch_step kernel vs scan body (8 PARSEC x "
          f"{KERNEL_INTERVALS} intervals, RESIPI + RESIPI_ALL): max rel "
          f"diff {worst:.3e}", flush=True)
    return deltas, worst


def phase_scale(jax, np, devices=None):
    """The topology grid; with `devices`, sharded over them and compared
    with the single-device run."""
    from repro.core import traffic
    from repro.core.simulator import (SUMMARY_KEYS, Arch, SimConfig,
                                      simulate, sweep_topology_batch,
                                      topology_point_config)

    sim = SimConfig().with_arch(Arch.RESIPI)
    cfg = sim.cfg.with_topology(n_chiplets=max(TOPOLOGY_GRID))
    keys = jax.random.split(jax.random.PRNGKey(1), len(traffic.APP_NAMES))
    traces = [traffic.generate_trace(a, ANCHOR_INTERVALS, k, cfg)
              for a, k in zip(traffic.APP_NAMES, keys)]
    out = sweep_topology_batch(traces, sim, n_chiplets=TOPOLOGY_GRID)
    lat = np.asarray(out["summary"]["mean_latency"])
    assert lat.shape == (len(traces), len(TOPOLOGY_GRID)), lat.shape
    assert np.all(np.isfinite(lat)), lat
    if devices is not None:
        sharded = sweep_topology_batch(traces, sim, devices=devices,
                                       n_chiplets=TOPOLOGY_GRID)
        assert sharded["sharding"]["devices"] == len(devices), \
            sharded["sharding"]
        for k in SUMMARY_KEYS:
            check_close(f"sharded grid summary[{k}]",
                        sharded["summary"][k], out["summary"][k])
        diff = max(max_rel_diff(sharded["summary"][k], out["summary"][k])
                   for k in SUMMARY_KEYS)
        print(f"[b] topology grid on {len(devices)} chips vs 1: max rel "
              f"diff {diff:.3e} ({sharded['sharding']})", flush=True)
        return diff
    # Padded grid points == unpadded simulate (test_topology_sweep's 1e-4).
    for i in (0, len(TOPOLOGY_GRID) - 1):
        c = TOPOLOGY_GRID[i]
        single = simulate(traffic.slice_trace(traces[0], c),
                          topology_point_config(sim, n_chiplets=c))
        for k in SUMMARY_KEYS:
            check_close(f"grid point {c} summary[{k}]",
                        out["summary"][k][0, i], single["summary"][k],
                        rtol=1e-4, atol=1e-4)
    print("[b] " + ", ".join(f"{c} chiplets {lat[:, i].mean():.2f}"
                             for i, c in enumerate(TOPOLOGY_GRID))
          + " cycles (mean latency over 8 PARSEC)", flush=True)

    # The largest grid point through the kernel: destinations and faults.
    from repro.core.faults import (GatewayFault, LinkFlap, LossDrift,
                                   PcmStuckCell, attach_faults,
                                   compile_faults)
    sim256 = dataclasses.replace(sim, cfg=cfg)
    c_max = cfg.n_chiplets
    tr = traffic.generate(
        traffic.ParsecSpec(app="canneal", n_intervals=KERNEL_INTERVALS),
        jax.random.PRNGKey(3), cfg, dest=True)
    frame = compile_faults(
        (GatewayFault(chiplet=0, slot=0, start=40),
         PcmStuckCell(chiplet=c_max // 3, slot=1, mode="on", start=90),
         LinkFlap(chiplet=c_max - 1, p_down=0.2, p_up=0.5, start=0),
         LossDrift(db_per_interval=0.01, start=150)),
        cfg, KERNEL_INTERVALS, seed=7)
    tr = array_leaves(attach_faults(tr, frame))
    sim256_k = dataclasses.replace(sim256, epoch_kernel=True)
    exe = compiled_with_kernel(
        jax, lambda t: simulate(t, sim256_k)["summary"], tr)
    kern = exe(tr)
    scan = simulate(tr, sim256)["summary"]
    for k in SUMMARY_KEYS:
        check_close(f"256-chiplet kernel vs scan summary[{k}]",
                    kern[k], scan[k])
    diff = summary_diff(kern, scan, SUMMARY_KEYS)
    print(f"[b] {c_max}-chiplet kernel simulate (dest + faults, "
          f"{KERNEL_INTERVALS} intervals): max rel diff vs scan {diff:.3e}",
          flush=True)
    return diff


def phase_codesign(jax, np, devices=None):
    from repro.core import pareto, traffic
    from repro.core.simulator import Arch, SimConfig

    base = SimConfig().with_arch(Arch.RESIPI)
    apps = ["blackscholes", "swaptions", "streamcluster", "facesim",
            "fluidanimate", "bodytrack", "canneal", "dedup"]
    cfg = base.cfg.with_topology(n_chiplets=max(CODESIGN_GRID))
    traces = [traffic.generate_trace(a, 12, k, cfg) for a, k in
              zip(apps, jax.random.split(jax.random.PRNGKey(5), len(apps)))]
    kw = dict(n_chiplets=CODESIGN_GRID, islands=4, generations=6,
              population=6, archive=24, migrate_every=3,
              knob_grids={"l_m": [0.008, 0.0152, 0.024, 0.032]}, seed=0)
    one = pareto.search_codesign(traces, base, devices=jax.devices()[:1],
                                 **kw)
    front = np.asarray([[e["objectives"][k] for k in
                         ("latency", "power_mw", "energy")]
                        for e in one["front"]])
    assert len(front) and np.all(np.isfinite(front)), front
    if devices is not None:
        sharded = pareto.search_codesign(traces, base, devices=devices, **kw)
        assert sharded["sharding"]["devices"] == len(devices), \
            sharded.get("sharding")
        for name, a, b in (
                ("archive objectives", sharded["archive"]["objectives"],
                 one["archive"]["objectives"]),
                ("island scores", sharded["island_scores"],
                 one["island_scores"])):
            check_close(f"sharded co-design {name}", np.nan_to_num(a),
                        np.nan_to_num(b))
        assert np.array_equal(sharded["archive"]["valid"],
                              one["archive"]["valid"])
        diff = max_rel_diff(np.nan_to_num(sharded["island_scores"]),
                            np.nan_to_num(one["island_scores"]))
        print(f"[c] co-design on {len(devices)} chips vs 1: island-score "
              f"max rel diff {diff:.3e} ({sharded['sharding']})", flush=True)
        return diff
    host = pareto.rescore_front_host(one, traces, base)
    check_close("front re-scored on the host path", host, front)
    print(f"[c] co-design front: {len(front)} points over "
          f"{one['candidate_evals']} candidate evals, re-scored max rel "
          f"diff {max_rel_diff(host, front):.3e}", flush=True)
    return len(front)


def phase_serve(jax, np):
    from repro.launch import serve
    from repro.serve.engine import replay_standalone

    server = serve.main(["--ticks", "32", "--lanes", "8", "--chunk", "8",
                         "--storm-at", "12", "--heal"])
    m = server.metrics()
    assert m["completed"] >= 1 and m["heals"] >= 1, m
    # On the CPU the served sums bit-match the replay (tests/test_serve.py).
    # On the TPU the batched tick and the unbatched chunk may reduce in
    # another order, so the floats are held to the kernel tests' 1e-6 and
    # the bit-exact sessions are counted; interval counts stay exact.
    keys = ("mean_latency", "mean_power_mw", "mean_energy")
    exact, worst = 0, 0.0
    for sess in server.completed:
        ref = replay_standalone(server.sim, sess)
        mine = sess.summary()
        assert float(ref["valid_intervals"]) == mine["valid_intervals"], \
            (sess.id, float(ref["valid_intervals"]), mine["valid_intervals"])
        for k in keys:
            check_close(f"session {sess.id} replay {k}", mine[k], ref[k])
        exact += all(float(ref[k]) == mine[k] for k in keys)
        worst = max(worst, max(max_rel_diff(mine[k], ref[k]) for k in keys))
    print(f"[d] serve: {m['completed']} sessions completed, {m['heals']} "
          f"heal(s); replay: {exact}/{len(server.completed)} bit-exact, max "
          f"rel diff {worst:.3e}", flush=True)
    return m["completed"]


def phase_residency(jax, np):
    import jax.numpy as jnp

    from repro.kernels.noc_step.kernel import noc_run_pallas
    from repro.kernels.noc_step.ops import build_topology, simulate_residency
    from repro.kernels.noc_step.ref import reference_noc_run

    cycles = RESIDENCY_CYCLES
    pro, _ = simulate_residency(0.10, g_active=1, wavelengths=16,
                                cycles=cycles, seed=5)
    res, _ = simulate_residency(0.10, g_active=2, wavelengths=4,
                                cycles=cycles, seed=5)
    assert pro.shape == res.shape == (4, 4)
    assert np.all(np.isfinite(pro)) and np.all(np.isfinite(res))

    next_mat, drain, buf, _ = build_topology(2, 4)
    n = next_mat.shape[0]
    arrivals = (jax.random.uniform(jax.random.PRNGKey(5), (cycles, n))
                < 0.1 / 16).astype(jnp.float32) * 4.0
    args = (arrivals, jnp.asarray(next_mat), jnp.asarray(drain),
            jnp.asarray(buf))
    exe = compiled_with_kernel(jax, lambda *a: noc_run_pallas(*a), *args)
    for what, got, want in zip(("residency", "occupancy", "drained"),
                               exe(*args), reference_noc_run(*args)):
        check_close(f"noc_step {what} vs lax.scan reference", got, want,
                    rtol=1e-4, atol=1e-2)     # test_kernels' oracle bound
    print(f"[e] residency ({cycles} cycles): PROWAVES max {pro.max():.2f} vs "
          f"ReSiPI max {res.max():.2f} flits", flush=True)
    return float(pro.max() / max(res.max(), 1e-9))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the topology grid and the co-design "
                         "search, sharded over 4 chips and then on 1")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import numpy as np

    from repro.runtime import cache as rcache

    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}")
    print(f"[cache] {rcache.enable_persistent_cache()}", flush=True)
    clock = CompileClock(jax)

    if args.four_chips:
        four = devices[:4]
        run_phase(clock, "b:scale-4chips",
                  lambda: phase_scale(jax, np, devices=four))
        run_phase(clock, "c:codesign-4chips",
                  lambda: phase_codesign(jax, np, devices=four))
    else:
        run_phase(clock, "a:paper-anchor", lambda: phase_anchor(jax, np))
        run_phase(clock, "b:scale", lambda: phase_scale(jax, np))
        run_phase(clock, "c:codesign", lambda: phase_codesign(jax, np))
        run_phase(clock, "d:serve", lambda: phase_serve(jax, np))
        run_phase(clock, "e:residency", lambda: phase_residency(jax, np))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
