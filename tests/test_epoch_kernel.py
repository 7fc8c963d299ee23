"""Fused epoch-scan kernel parity: `kernels.epoch_step` vs the lax.scan body.

The kernel runs the whole interval loop — latency model, power model,
gateway controller, fault masking, destination-aware routing — inside one
`pallas_call`, carrying the per-chiplet gateway vector in VMEM scratch
across grid steps. Its oracle is `epoch_step.ref.epoch_run_reference`,
literally `lax.scan(make_step(...))`, i.e. what every entry point runs when
`SimConfig.epoch_kernel` is off. These tests pin:

  * record + final-state parity at 1e-6 in interpret mode: clean, ragged
    `t_mask` (tail-padded and fully masked — carry freeze), full fault
    frames (gateway kills, stuck PCM cells, link flaps, loss drift),
    destination matrices, and both RESIPI controllers;
  * every public entry point (`simulate`, `sweep`, `simulate_batch`,
    `sweep_workload`, `SimSession`, `session_tick`) produces the same
    numbers with `epoch_kernel=True`;
  * compile-once discipline survives: one scan-body trace per shape, warm
    calls hit the cache;
  * the arch guard (PROWAVES/AWGR run the scan body at the `_scan_trace`
    gate, which counts kernel traces apart; the raw kernel op rejects them
    loudly).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simulator as S
from repro.core import traffic
from repro.core.faults import (GatewayFault, LinkFlap, LossDrift,
                               PcmStuckCell, attach_faults, compile_faults)
from repro.core.simulator import Arch, SimConfig
from repro.kernels.epoch_step.ops import epoch_run_pallas
from repro.kernels.epoch_step.ref import epoch_run_reference

SIM = SimConfig()
SIM_K = dataclasses.replace(SIM, epoch_kernel=True)

FAULT_SPECS = (GatewayFault(chiplet=0, slot=0, start=2),
               PcmStuckCell(chiplet=1, slot=1, mode="on", start=4),
               LinkFlap(chiplet=2, p_down=0.3, p_up=0.5, start=0),
               LossDrift(db_per_interval=0.02, start=3))


def _xs_of(trace, sim):
    ext, mem, intra, ext_frac, t_mask, dmat = S._trace_arrays(trace)
    xs = (ext, mem, intra, jnp.broadcast_to(ext_frac, mem.shape), t_mask)
    flt = S._trace_faults(trace)
    if flt is not None:
        xs = xs + tuple(flt)
    return xs, dmat, flt is not None


def _assert_run_parity(trace, sim, rtol=1e-6, atol=1e-6):
    """Raw kernel vs raw reference on one trace: records + final state."""
    xs, dmat, faulted = _xs_of(trace, sim)
    state0 = S._initial_state(sim)
    tables = S.selection_tables_jax(sim.cfg)
    fs_k, recs_k = epoch_run_pallas(state0, xs, sim, tables,
                                    dest=dmat, faulted=faulted,
                                    interpret=True)
    fs_r, recs_r = epoch_run_reference(state0, xs, sim, tables,
                                       dest=dmat, faulted=faulted)
    assert set(recs_k) == set(recs_r), (set(recs_k), set(recs_r))
    for k in recs_r:
        np.testing.assert_allclose(
            np.asarray(recs_k[k], np.float32),
            np.asarray(recs_r[k], np.float32),
            rtol=rtol, atol=atol, err_msg=f"records[{k}]")
    for lk, lr in zip(jax.tree.leaves(fs_k), jax.tree.leaves(fs_r)):
        np.testing.assert_allclose(np.asarray(lk, np.float32),
                                   np.asarray(lr, np.float32),
                                   rtol=rtol, atol=atol,
                                   err_msg="final state")


@pytest.mark.parametrize("arch", [Arch.RESIPI, Arch.RESIPI_ALL])
def test_kernel_matches_reference_clean(arch):
    tr = traffic.generate(traffic.UniformSpec(n_intervals=37),
                          jax.random.PRNGKey(0))
    _assert_run_parity(tr, SIM.with_arch(arch))


@pytest.mark.parametrize("spec", [
    traffic.PermutationSpec(pattern="transpose", n_intervals=29,
                            mean_load=0.05),
    traffic.ParsecSpec(app="dedup", n_intervals=23),
])
def test_kernel_matches_reference_dest(spec):
    tr = traffic.generate(spec, jax.random.PRNGKey(1), dest=True)
    _assert_run_parity(tr, SIM)
    _assert_run_parity(tr, SIM.with_arch(Arch.RESIPI_ALL))


@pytest.mark.parametrize("n_valid", [0, 9])
def test_kernel_matches_reference_tmask(n_valid):
    """Masked intervals freeze the carry — including the all-masked trace,
    whose final state must equal the initial state on both engines."""
    tr = traffic.generate(traffic.UniformSpec(n_intervals=16),
                          jax.random.PRNGKey(2))
    mask = np.zeros((16,), np.float32)
    mask[:n_valid] = 1.0
    tr = dict(tr, t_mask=jnp.asarray(mask))
    _assert_run_parity(tr, SIM)


@pytest.mark.parametrize("arch", [Arch.RESIPI, Arch.RESIPI_ALL])
def test_kernel_matches_reference_faults(arch):
    tr = traffic.generate(traffic.UniformSpec(n_intervals=21),
                          jax.random.PRNGKey(3))
    frame = compile_faults(FAULT_SPECS, SIM.cfg, 21, seed=7)
    _assert_run_parity(attach_faults(tr, frame), SIM.with_arch(arch))


def test_kernel_matches_reference_faults_dest_tmask():
    """The full stack at once: faults + destination matrix + ragged tail."""
    tr = traffic.generate(
        traffic.PermutationSpec(pattern="tornado", n_intervals=18,
                                mean_load=0.05),
        jax.random.PRNGKey(4), dest=True)
    frame = compile_faults(FAULT_SPECS, SIM.cfg, 18, seed=11)
    tr = attach_faults(tr, frame)
    mask = np.ones((18,), np.float32)
    mask[13:] = 0.0
    _assert_run_parity(dict(tr, t_mask=jnp.asarray(mask)), SIM)


@pytest.mark.parametrize("arch", [Arch.PROWAVES, Arch.AWGR])
def test_kernel_rejects_unsupported_arch(arch):
    """The raw op refuses non-RESIPI controllers (their lambda controllers
    are not fused); the engine-level gate runs the scan body instead."""
    sim = SIM.with_arch(arch)
    tr = traffic.generate(traffic.UniformSpec(n_intervals=8),
                          jax.random.PRNGKey(5))
    xs, dmat, _ = _xs_of(tr, sim)
    with pytest.raises(ValueError, match="epoch_step"):
        epoch_run_pallas(S._initial_state(sim), xs, sim,
                         S.selection_tables_jax(sim.cfg), interpret=True)


@pytest.mark.parametrize("arch", list(Arch))
def test_simulate_entrypoint_parity(arch):
    """`simulate` with epoch_kernel=True matches the scan engine for every
    arch — RESIPI archs through the kernel, the rest through the fallback."""
    sim, sim_k = SIM.with_arch(arch), SIM_K.with_arch(arch)
    tr = traffic.generate(traffic.ParsecSpec(app="canneal", n_intervals=19),
                          jax.random.PRNGKey(6), dest=True)
    out_k, out_r = S.simulate(tr, sim_k), S.simulate(tr, sim)
    for k, v in out_r["summary"].items():
        np.testing.assert_allclose(np.asarray(out_k["summary"][k]),
                                   np.asarray(v), rtol=1e-6, atol=1e-6,
                                   err_msg=f"summary[{k}]")
    for k, v in out_r["records"].items():
        np.testing.assert_allclose(
            np.asarray(out_k["records"][k], np.float32),
            np.asarray(v, np.float32), rtol=1e-6, atol=1e-6,
            err_msg=f"records[{k}]")


def test_sweep_entrypoint_parity():
    """Runtime-grid sweeps vmap the kernel with traced overrides (l_m etc.
    ride the SMEM params row, not the cache key)."""
    tr = traffic.generate(traffic.UniformSpec(n_intervals=15),
                          jax.random.PRNGKey(7))
    grids = dict(l_m=[0.01, 0.0152, 0.03], wavelengths=[2, 4, 4])
    out_k = S.sweep(tr, SIM_K, **grids)
    out_r = S.sweep(tr, SIM, **grids)
    for k, v in out_r["summary"].items():
        np.testing.assert_allclose(np.asarray(out_k["summary"][k]),
                                   np.asarray(v), rtol=1e-6, atol=1e-6,
                                   err_msg=f"summary[{k}]")


def test_simulate_batch_and_workload_parity():
    specs = [traffic.UniformSpec(n_intervals=10),
             traffic.PermutationSpec(pattern="transpose", n_intervals=14,
                                     mean_load=0.05)]
    traces = [traffic.generate(s, jax.random.PRNGKey(i), dest=True)
              for i, s in enumerate(specs)]
    bk, br = S.simulate_batch(traces, SIM_K), S.simulate_batch(traces, SIM)
    for k, v in br["summary"].items():
        np.testing.assert_allclose(np.asarray(bk["summary"][k]),
                                   np.asarray(v), rtol=1e-6, atol=1e-6,
                                   err_msg=f"batch summary[{k}]")
    wk = S.sweep_workload(specs, SIM_K, seed=0, dest=True)
    wr = S.sweep_workload(specs, SIM, seed=0, dest=True)
    for k, v in wr["summary"].items():
        np.testing.assert_allclose(np.asarray(wk["summary"][k]),
                                   np.asarray(v), rtol=1e-6, atol=1e-6,
                                   err_msg=f"workload summary[{k}]")


def test_session_chunked_carry_parity():
    """Chunked streaming through the kernel == one-shot simulate: the carry
    (controller g, packets_seen, prev_active) crosses chunk boundaries
    through the VMEM-scratch final-state reconstruction."""
    tr = traffic.generate(traffic.BurstySpec(n_intervals=24),
                          jax.random.PRNGKey(8))
    one = S.simulate(tr, SIM_K)
    sess = S.SimSession.init(SIM_K)
    recs = [sess.step_chunk(ch)["records"]
            for ch in traffic.chunk_trace(tr, 8)]
    for k in one["records"]:
        np.testing.assert_allclose(
            np.concatenate([np.asarray(r[k], np.float32) for r in recs]),
            np.asarray(one["records"][k], np.float32),
            rtol=1e-6, atol=1e-6, err_msg=f"chunked records[{k}]")
    for k, v in one["summary"].items():
        np.testing.assert_allclose(np.asarray(sess.summary()[k]),
                                   np.asarray(v), rtol=1e-5, atol=1e-6,
                                   err_msg=f"session summary[{k}]")


def test_session_tick_parity():
    """The server's vmapped tick: live, frozen, and half-masked lanes all
    match the scan engine, with and without destination matrices."""
    tr = traffic.generate(traffic.UniformSpec(n_intervals=8),
                          jax.random.PRNGKey(9))
    tables = S.selection_tables_jax(SIM.cfg)
    states = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[S._initial_state(SIM_K) for _ in range(3)])
    t = 6
    batch = {"ext_load": jnp.stack([tr["ext_load"][:t]] * 3),
             "mem_load": jnp.stack([tr["mem_load"][:t]] * 3),
             "int_load": jnp.stack([tr["int_load"][:t]] * 3),
             "ext_frac": jnp.stack([tr["ext_frac"]] * 3),
             "t_mask": jnp.stack([
                 jnp.ones((t,)), jnp.zeros((t,)),
                 jnp.concatenate([jnp.ones((3,)), jnp.zeros((3,))])])}
    dmat = traffic.destination_matrix_jax(
        traffic.PermutationSpec(pattern="transpose", mean_load=0.05),
        SIM.cfg)
    for b in (batch, dict(batch, dest=jnp.stack([dmat] * 3))):
        out_k = S.session_tick(states, b, tables, SIM_K)
        out_r = S.session_tick(states, b, tables, SIM)
        for lk, lr in zip(jax.tree.leaves(out_k), jax.tree.leaves(out_r)):
            np.testing.assert_allclose(np.asarray(lk, np.float32),
                                       np.asarray(lr, np.float32),
                                       rtol=1e-6, atol=1e-6)


def test_kernel_compile_once():
    """One scan-body trace per shape with the kernel on; warm calls reuse
    the executable (the fused body must not break the jit cache keys)."""
    tr = traffic.generate(traffic.UniformSpec(n_intervals=12),
                          jax.random.PRNGKey(10))
    S.clear_engine_caches()
    S.reset_engine_stats()
    S.simulate(tr, SIM_K)
    stats = S.engine_stats()
    assert stats["simulate_traces"] == 1, stats
    assert stats["kernel_traces"] == 1, stats      # the kernel body ran
    S.simulate(tr, SIM_K)
    S.simulate(dict(tr, ext_load=tr["ext_load"] * 2.0), SIM_K)
    assert S.engine_stats()["simulate_traces"] == 1, S.engine_stats()
