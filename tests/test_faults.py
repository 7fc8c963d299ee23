"""Fault-injection invariants (the robustness PR's tentpole contracts).

Pinned here, mirroring the PR 2/4 masking invariants:

  * never-fire parity — a fault frame whose windows never intersect the
    simulated horizon matches fault-free `simulate` at 1e-6, per arch;
  * dead-slot equivalence — hard-failing slots is *provably* identical to
    never having them: pinned g=4 with slots 2,3 failed on every chiplet
    equals pinned g=2 fault-free in every latency/power/energy reduction;
  * the fault grid is an ordinary sweep axis (vmap parity, one executable);
  * chunk alignment — fault events ride the trace transforms, so a
    streamed faulted session bit-matches the one-shot faulted scan;
  * the noc_step kernel's time-varying valid_mask path matches its lax.scan
    oracle and degrades to the static path bit-for-bit.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults, traffic
from repro.core import simulator as S
from repro.core.simulator import (Arch, SimConfig, SimSession, engine_stats,
                                  reset_engine_stats, simulate,
                                  simulate_batch, stack_traces, sweep_faults)

T = 12


def _trace(seed=0, t=T):
    return traffic.generate_trace("dedup", t, jax.random.PRNGKey(seed))


def _close(a, b, rtol=1e-6, **kw):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, **kw)


# ---------------------------------------------------------------------------
# Never-fire parity + dead-slot equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", list(Arch))
def test_never_firing_frame_matches_fault_free(arch):
    sim = SimConfig().with_arch(arch)
    tr = _trace()
    clean = simulate(tr, sim)
    frame = faults.compile_faults(
        [faults.GatewayFault(start=T, chiplet=0, slot=0),
         faults.PcmStuckCell(start=T, chiplet=1, slot=1, mode="on"),
         faults.LossDrift(start=T, db_per_interval=1.0)], sim.cfg, T)
    faulted = simulate(faults.attach_faults(tr, frame), sim)
    for k in clean["summary"]:
        _close(faulted["summary"][k], clean["summary"][k],
               err_msg=f"{arch}: never-firing frame diverged on {k}")
    for k in ("latency", "power_mw", "g"):
        _close(faulted["records"][k], clean["records"][k],
               err_msg=f"{arch}: never-firing frame diverged on records"
                       f"[{k}]")


def test_dead_slots_equal_smaller_network():
    """Hard-failed slots contribute zero to EVERY reduction: pinned g=4
    with slots 2,3 dead on all chiplets == pinned g=2 fault-free."""
    from benchmarks.common import fixed_gateway_config

    tr = _trace(1)
    big = fixed_gateway_config(4)
    frame = faults.compile_faults(
        [faults.GatewayFault(start=0, chiplet=c, slot=s)
         for c in range(big.cfg.n_chiplets) for s in (2, 3)], big.cfg, T)
    hurt = simulate(faults.attach_faults(tr, frame), big)
    small = simulate(tr, fixed_gateway_config(2))
    for k in ("mean_latency", "mean_power_mw", "mean_energy",
              "total_reconfig_nj"):
        _close(hurt["summary"][k], small["summary"][k],
               err_msg=f"dead slots leaked into {k}")
    # The records expose both views: effective g collapses to the
    # survivors, the controller's desire stays at 4.
    assert np.all(np.asarray(hurt["records"]["g"]) == 2)
    assert np.all(np.asarray(hurt["records"]["g_desired"]) == 4)
    assert np.all(np.asarray(hurt["records"]["failed_slots"])
                  == 2 * big.cfg.n_chiplets)


def test_stuck_on_is_power_only():
    from benchmarks.common import fixed_gateway_config

    sim = fixed_gateway_config(2)
    tr = _trace(2)
    clean = simulate(tr, sim)["summary"]
    frame = faults.compile_faults(
        [faults.PcmStuckCell(start=0, chiplet=0, slot=3, mode="on")],
        sim.cfg, T)
    stuck = simulate(faults.attach_faults(tr, frame), sim)["summary"]
    _close(stuck["mean_latency"], clean["mean_latency"])
    _close(stuck["mean_gateways"], clean["mean_gateways"])
    assert float(stuck["mean_power_mw"]) > float(clean["mean_power_mw"])


def test_loss_drift_costs_power_monotonically():
    sim = SimConfig()
    tr = _trace(3)
    clean = float(simulate(tr, sim)["summary"]["mean_power_mw"])
    drifted = simulate(faults.attach_faults(tr, faults.compile_faults(
        [faults.LossDrift(start=0, db_per_interval=0.2, max_db=3.0)],
        sim.cfg, T)), sim)
    assert float(drifted["summary"]["mean_power_mw"]) > clean
    # Every interval pays for the extra loss (the laser-power term also
    # scales with traffic, so the per-interval delta is positive but not
    # strictly monotone), and a steeper ramp costs strictly more overall.
    delta = (np.asarray(drifted["records"]["power_mw"])
             - np.asarray(simulate(tr, sim)["records"]["power_mw"]))
    assert np.all(delta > 0.0), delta
    steeper = simulate(faults.attach_faults(tr, faults.compile_faults(
        [faults.LossDrift(start=0, db_per_interval=0.5, max_db=6.0)],
        sim.cfg, T)), sim)
    assert (float(steeper["summary"]["mean_power_mw"])
            > float(drifted["summary"]["mean_power_mw"]))


def test_link_flap_deterministic_and_kills_chiplet():
    sim = SimConfig()
    spec = faults.LinkFlap(start=0, chiplet=1, p_down=1.0, p_up=0.0)
    f1 = faults.compile_faults([spec], sim.cfg, T, seed=7)
    f2 = faults.compile_faults([spec], sim.cfg, T, seed=7)
    np.testing.assert_array_equal(f1["gw_ok"], f2["gw_ok"])
    # p_down=1, p_up=0: down from the first interval, whole chiplet dead.
    assert np.all(f1["gw_ok"][:, 1, :] == 0.0)
    assert np.all(f1["gw_ok"][:, 0, :] == 1.0)
    # A different seed draws a different chain for stochastic parameters.
    spec2 = faults.LinkFlap(start=0, chiplet=1, p_down=0.5, p_up=0.5)
    a = faults.compile_faults([spec2], sim.cfg, T, seed=0)
    b = faults.compile_faults([spec2], sim.cfg, T, seed=1)
    assert not np.array_equal(a["gw_ok"], b["gw_ok"])


# ---------------------------------------------------------------------------
# Engine integration: sweep axis, batching, streaming, executables
# ---------------------------------------------------------------------------

def test_sweep_faults_matches_one_trace_simulate():
    sim = SimConfig()
    tr = _trace(4)
    frames = [faults.no_faults(sim.cfg, T),
              faults.compile_faults([faults.GatewayFault(start=1, chiplet=0,
                                                         slot=0)],
                                    sim.cfg, T),
              faults.compile_faults([faults.LossDrift(start=2,
                                                      db_per_interval=0.3)],
                                    sim.cfg, T)]
    reset_engine_stats()
    sw = sweep_faults(tr, sim, frames)
    assert engine_stats()["simulate_traces"] == 1
    for i, fr in enumerate(frames):
        one = simulate(faults.attach_faults(tr, fr), sim)["summary"]
        for k in ("mean_latency", "mean_power_mw", "mean_energy"):
            _close(sw["summary"][k][i], one[k],
                   err_msg=f"fault lane {i} diverged on {k}")


def test_sweep_faults_zips_with_runtime_grids():
    sim = SimConfig()
    tr = _trace(4)
    frames = [faults.no_faults(sim.cfg, T)] * 2
    out = sweep_faults(tr, sim, frames, l_m=jnp.asarray([0.01, 0.03]))
    assert np.asarray(out["summary"]["mean_latency"]).shape == (2,)
    with pytest.raises(ValueError, match="lane-for-lane"):
        sweep_faults(tr, sim, frames, l_m=jnp.asarray([0.01, 0.02, 0.03]))


def test_sweep_faults_rejects_attached_trace_and_bad_horizon():
    sim = SimConfig()
    tr = _trace(4)
    fr = faults.no_faults(sim.cfg, T)
    with pytest.raises(ValueError, match="clean"):
        sweep_faults(faults.attach_faults(tr, fr), sim, [fr])
    with pytest.raises(ValueError, match="intervals"):
        sweep_faults(tr, sim, [faults.no_faults(sim.cfg, T + 1)])


def test_simulate_batch_with_fault_frames():
    sim = SimConfig()
    trs = [_trace(5), _trace(6)]
    frames = [faults.no_faults(sim.cfg, T),
              faults.compile_faults([faults.GatewayFault(start=0, chiplet=0,
                                                         slot=0)],
                                    sim.cfg, T)]
    batch = [faults.attach_faults(t, f) for t, f in zip(trs, frames)]
    out = simulate_batch(batch, sim)
    for i in range(2):
        _close(out["summary"]["mean_latency"][i],
               simulate(batch[i], sim)["summary"]["mean_latency"])
    with pytest.raises(ValueError, match="uniformly"):
        stack_traces([batch[0], trs[1]])


def test_partial_fault_frame_raises():
    sim = SimConfig()
    tr = dict(_trace(7), gw_ok=np.ones((T, sim.cfg.n_chiplets,
                                        sim.cfg.max_gateways_per_chiplet),
                                       np.float32))
    with pytest.raises(ValueError, match="missing"):
        simulate(tr, sim)


def test_attach_faults_validates():
    sim = SimConfig()
    tr = _trace(8)
    with pytest.raises(ValueError, match="intervals"):
        faults.attach_faults(tr, faults.no_faults(sim.cfg, T + 3))
    with pytest.raises(ValueError, match="missing"):
        faults.attach_faults(tr, {"gw_ok": np.ones((T, 4, 4))})
    attached = faults.attach_faults(tr, faults.no_faults(sim.cfg, T))
    stripped = faults.strip_faults(attached)
    assert set(faults.FAULT_KEYS).isdisjoint(stripped)
    assert set(traffic.TRACE_KEYS) <= set(stripped)


def test_faulted_session_chunks_match_one_shot():
    """pad/chunk/concat carry the fault arrays: streamed == one-shot."""
    sim = SimConfig()
    t_total = 24
    tr = _trace(9, t=t_total)
    frame = faults.compile_faults(
        [faults.GatewayFault(start=5, end=17, chiplet=0, slot=0),
         faults.LossDrift(start=8, db_per_interval=0.1)], sim.cfg, t_total)
    attached = faults.attach_faults(tr, frame)
    one = simulate(attached, sim)

    session = SimSession.init(sim)
    recs = [session.step_chunk(ch)["records"]
            for ch in traffic.chunk_trace(attached, 8)]
    cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *recs)
    for k in ("latency", "power_mw", "g", "failed_slots"):
        np.testing.assert_array_equal(
            np.asarray(cat[k]), np.asarray(one["records"][k]),
            err_msg=f"streamed faulted records[{k}] diverged")
    _close(session.summary()["mean_latency"],
           one["summary"]["mean_latency"])


def test_swap_placement_is_zero_recompile():
    sim = SimConfig()
    tr = _trace(10)
    session = SimSession.init(sim)
    chunks = list(traffic.chunk_trace(tr, 6))
    session.step_chunk(chunks[0])
    reset_engine_stats()
    session.swap_placement(((0, 0), (3, 3), (0, 3), (3, 0)))
    session.step_chunk(chunks[1])
    assert engine_stats()["simulate_traces"] == 0, \
        "live re-placement re-traced the chunk executable"
    assert session.placement == ((0, 0), (3, 3), (0, 3), (3, 0))


def test_faults_reject_padded_topology_paths():
    from repro.core.simulator import sweep_topology

    sim = SimConfig()
    tr = faults.attach_faults(_trace(11),
                              faults.no_faults(sim.cfg, T))
    with pytest.raises(ValueError, match="topology"):
        sweep_topology(tr, sim, n_chiplets=[4])


# ---------------------------------------------------------------------------
# One jitted program per entry point: the fault frame is an optional pytree
# ---------------------------------------------------------------------------

def _clean_and_faulted_calls(entry, sim):
    """(jitted function, clean call, faulted call) of one entry point; the
    faulted call carries a frame that never fires."""
    tr = _trace(12)
    frame = faults.compile_faults(
        [faults.GatewayFault(start=T, chiplet=0, slot=0)], sim.cfg, T)
    hurt = faults.attach_faults(tr, frame)
    if entry == "simulate":
        return (S._simulate_jit, lambda: simulate(tr, sim),
                lambda: simulate(hurt, sim))
    if entry == "simulate_batch":
        return (S._sweep_workload_jit, lambda: simulate_batch([tr, tr], sim),
                lambda: simulate_batch([hurt, hurt], sim))
    if entry == "sweep_faults":
        lm = [0.01, 0.02]
        return (S._sweep_jit, lambda: S.sweep(tr, sim, l_m=lm),
                lambda: sweep_faults(tr, sim, [frame, frame], l_m=lm))
    if entry == "step_chunk":
        session = SimSession.init(sim)
        clean = list(traffic.chunk_trace(tr, 6))
        hurt_chunks = list(traffic.chunk_trace(hurt, 6))
        return (S._session_chunk_jit,
                lambda: session.step_chunk(clean[0]),
                lambda: session.step_chunk(hurt_chunks[1]))
    states = S.init_session_states(sim, 2)
    batch = stack_traces([tr, tr], pad=True)
    tables = S.selection_tables_jax(sim.cfg)
    return (S._session_tick_jit,
            lambda: S.session_tick(states, batch, tables, sim),
            lambda: S.session_tick(states, batch, tables, sim, frame=frame))


@pytest.mark.parametrize("entry", ["simulate", "simulate_batch",
                                   "sweep_faults", "step_chunk",
                                   "session_tick"])
def test_entry_point_is_one_program_clean_and_faulted(entry):
    """A clean call and a faulted call are two cache entries of ONE jitted
    function (two traces); repeating either traces nothing."""
    sim = SimConfig()
    jit_fn, clean, hurt = _clean_and_faulted_calls(entry, sim)
    S.clear_engine_caches()
    reset_engine_stats()
    jax.block_until_ready((clean(), hurt()))
    assert engine_stats()["simulate_traces"] == 2, engine_stats()
    assert jit_fn._cache_size() == 2
    reset_engine_stats()
    jax.block_until_ready((clean(), hurt()))
    assert engine_stats()["simulate_traces"] == 0, engine_stats()
    assert jit_fn._cache_size() == 2


@pytest.mark.parametrize("name", ["_sweep_batch_jit",
                                  "_sweep_topology_batch_jit",
                                  "_session_tick_jit"])
def test_benchmark_entry_program_names(name, monkeypatch):
    """perfbench finds the cells' entry programs in device traces by name
    (`jit_<name>`): the public entry point must dispatch a program of that
    name."""
    jit_fn = getattr(S, name)
    modules = []

    def spy(*args, **kw):
        text = jit_fn.lower(*args, **kw).as_text()
        modules.append(re.search(r"module @(\S+)", text).group(1))
        return jit_fn(*args, **kw)

    monkeypatch.setattr(S, name, spy)
    sim = SimConfig()
    tr = _trace(13)
    if name == "_sweep_batch_jit":
        S.sweep_batch([tr, tr], sim, l_m=[0.01, 0.02])
    elif name == "_sweep_topology_batch_jit":
        S.sweep_topology_batch([tr, tr], sim, n_chiplets=[4, 4],
                               gateways_per_chiplet=[4, 2])
    else:
        S.session_tick(S.init_session_states(sim, 2),
                       stack_traces([tr, tr], pad=True),
                       S.selection_tables_jax(sim.cfg), sim)
    assert modules == [f"jit_{name}"]


# ---------------------------------------------------------------------------
# Spec/compile semantics + the closed-loop environment pieces
# ---------------------------------------------------------------------------

def test_spec_validation():
    with pytest.raises(ValueError, match="start"):
        faults.GatewayFault(start=-1)
    with pytest.raises(ValueError, match="end"):
        faults.GatewayFault(start=5, end=3)
    with pytest.raises(ValueError, match="slot"):
        faults.compile_faults([faults.GatewayFault(slot=99)], n_intervals=4)
    with pytest.raises(ValueError, match="chiplet"):
        faults.compile_faults([faults.GatewayFault(chiplet=99)],
                              n_intervals=4)
    with pytest.raises(ValueError, match="mode"):
        faults.PcmStuckCell(mode="sideways")
    with pytest.raises(TypeError, match="FaultSpec"):
        faults.compile_faults(["not a spec"], n_intervals=4)
    assert hash(faults.GatewayFault(start=0)) is not None


def test_position_fault_heals_by_replacement():
    """A position-targeted fault is a no-op once no gateway sits there."""
    cfg = SimConfig().cfg
    placement = faults.normalize_placement(
        faults.resolve_gateway_positions(cfg), cfg)
    spec = faults.GatewayFault(start=0, chiplet=0, position=placement[0])
    biting = faults.compile_faults([spec], cfg, 4)
    assert np.any(biting["gw_ok"] == 0.0)
    moved = [(x, y) for (x, y) in [(1, 1), (2, 2), (1, 2), (2, 1)]]
    healed = faults.compile_faults([spec], cfg.with_placement(tuple(moved)),
                                   4)
    assert np.all(healed["gw_ok"] == 1.0)


def test_fault_injector_chunks_and_status_register():
    sim = SimConfig()
    cfg = sim.cfg
    placement = faults.normalize_placement(
        faults.resolve_gateway_positions(cfg), cfg)
    inj = faults.FaultInjector(
        [faults.GatewayFault(start=8, end=16, chiplet=0,
                             position=placement[0])], 24)
    full = inj.frame_for(cfg, 0, 24)
    for t0 in (0, 8, 16):
        part = inj.frame_for(cfg, t0, t0 + 8)
        np.testing.assert_array_equal(part["gw_ok"],
                                      full["gw_ok"][t0:t0 + 8])
    assert inj.failed_positions(4) == []
    assert inj.failed_positions(8) == [placement[0]]
    assert inj.failed_positions(16) == []
    with pytest.raises(ValueError, match="horizon"):
        inj.frame_for(cfg, 20, 30)
    tr = _trace(12, t=8)
    chunk = inj.inject(tr, cfg, 8)
    assert np.all(np.asarray(chunk["gw_ok"][:, 0, 0]) == 0.0)


def test_fault_injector_frame_cache_is_lru_bounded():
    """The placement-keyed frame cache is LRU with a hard bound: a serving
    loop that heals repeatedly (every heal = a new placement key) cannot
    grow it without bound, the least-recently-USED key is the one evicted,
    and an evicted placement recompiles to an identical frame."""
    cfg = SimConfig().cfg
    inj = faults.FaultInjector(
        [faults.GatewayFault(start=0, chiplet=0, position=(0, 0))], 8,
        cache_size=2)
    base = faults.normalize_placement(
        faults.resolve_gateway_positions(cfg), cfg)
    placements = [base, ((1, 1), (2, 2), (1, 2), (2, 1)),
                  ((0, 0), (3, 3), (0, 3), (3, 0))]
    cfgs = [cfg.with_placement(p) for p in placements]

    first = {k: np.asarray(v)
             for k, v in inj.frame_for(cfgs[0], 0, 8).items()}
    inj.frame_for(cfgs[1], 0, 8)
    assert len(inj._frames) == 2
    inj.frame_for(cfgs[0], 0, 8)          # touch: placements[0] is now MRU
    inj.frame_for(cfgs[2], 0, 8)          # evicts placements[1], not [0]
    assert len(inj._frames) == 2
    keys = list(inj._frames)
    assert faults.normalize_placement(placements[0], cfg) in keys
    assert faults.normalize_placement(placements[1], cfg) not in keys
    # The evicted placement recompiles bit-identically on re-request.
    again = inj.frame_for(cfgs[0], 0, 8)
    for k in first:
        np.testing.assert_array_equal(first[k], np.asarray(again[k]))
    with pytest.raises(ValueError, match="cache_size"):
        faults.FaultInjector([], 8, cache_size=0)


def test_placement_reconfig_cost():
    a = ((1, 0), (2, 3), (0, 2), (3, 1))
    b = ((1, 1), (2, 3), (0, 2), (3, 1))
    zero = faults.placement_reconfig_cost(a, a)
    assert zero == {"moved_gateways": 0, "pcm_nj": 0.0, "stall_cycles": 0}
    one = faults.placement_reconfig_cost(a, b)
    assert one["moved_gateways"] == 2          # site removed + site added
    assert one["pcm_nj"] > 0 and one["stall_cycles"] > 0


# ---------------------------------------------------------------------------
# noc_step kernel: time-varying valid_mask path
# ---------------------------------------------------------------------------

def _noc_problem(t=32, r=9, seed=0):
    rng = np.random.RandomState(seed)
    arr = jnp.asarray(rng.rand(t, r).astype(np.float32) * 0.5)
    nmat = np.zeros((r, r), np.float32)
    for i in range(r - 1):
        nmat[i, i + 1] = 1.0
    drain = np.zeros((r,), np.float32)
    drain[r - 1] = 2.0
    return arr, jnp.asarray(nmat), jnp.asarray(drain), \
        jnp.full((r,), 4.0, jnp.float32)


def test_kernel_tv_mask_all_ones_is_static_bitwise():
    from repro.kernels.noc_step.kernel import noc_run_pallas

    arr, nmat, drain, buf = _noc_problem()
    static = noc_run_pallas(arr, nmat, drain, buf, t_chunk=8,
                            interpret=True)
    tv = noc_run_pallas(arr, nmat, drain, buf,
                        valid_mask_t=jnp.ones(arr.shape, jnp.float32),
                        t_chunk=8, interpret=True)
    for a, b in zip(tv, static):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_kernel_tv_mask_matches_reference_and_kills_lane():
    from repro.kernels.noc_step.kernel import noc_run_pallas
    from repro.kernels.noc_step.ref import reference_noc_run

    arr, nmat, drain, buf = _noc_problem()
    tv = np.ones(arr.shape, np.float32)
    tv[10:, 3] = 0.0                        # lane 3 dies mid-run
    tv = jnp.asarray(tv)
    got = noc_run_pallas(arr, nmat, drain, buf, valid_mask_t=tv,
                         t_chunk=8, interpret=True)
    ref = reference_noc_run(arr, nmat, drain, buf, valid_mask_t=tv)
    for a, b in zip(got, ref):
        _close(a, b, atol=1e-6)
    # the dead lane is provably dead: zero final occupancy
    assert float(got[1][3]) == 0.0
    with pytest.raises(ValueError, match="valid_mask_t"):
        noc_run_pallas(arr, nmat, drain, buf,
                       valid_mask_t=jnp.ones((3, 3)), interpret=True)
