"""Workload-polymorphic engine tests: ragged-T batching, workload sweeps,
streaming sessions, and the kernel-level time mask.

The load-bearing invariants of PR 4:

  * time-masking — a tail-padded trace simulates identically to its
    unpadded original for EVERY architecture (1e-6), the time-axis
    analogue of the PR 2 chiplet-masking invariant;
  * one executable — a K-workload sweep / ragged batch is ONE scan-body
    trace, and warm re-calls re-trace nothing;
  * streaming — a chunked `SimSession` run bit-matches one-shot
    `simulate` records and reproduces its summary.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults, traffic
from repro.core.constants import NETWORK
from repro.core.simulator import (Arch, SimConfig, SimSession, engine_stats,
                                  reset_engine_stats, simulate,
                                  simulate_batch, simulate_stream,
                                  stack_traces, sweep_batch,
                                  sweep_topology, sweep_workload,
                                  topology_point_config)
from repro.kernels.noc_step.kernel import noc_run_pallas
from repro.kernels.noc_step.ops import build_topology
from repro.kernels.noc_step.ref import reference_noc_run

SUMMARY_KEYS = ("mean_latency", "mean_power_mw", "mean_energy",
                "mean_gateways", "mean_wavelengths", "saturated_frac",
                "total_reconfig_nj")


@pytest.fixture(scope="module")
def ragged_traces():
    apps = [("dedup", 21), ("canneal", 14), ("facesim", 9)]
    return [traffic.generate_trace(a, t, jax.random.PRNGKey(i))
            for i, (a, t) in enumerate(apps)]


_chunks = traffic.chunk_trace


# ---------------------------------------------------------------------------
# Ragged-T batching (the time-masking invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", list(Arch))
def test_padded_lane_matches_unpadded_per_arch(ragged_traces, arch):
    """Padded lane k == unpadded simulate(trace k) at 1e-6, every arch."""
    sim = SimConfig().with_arch(arch)
    out = simulate_batch(ragged_traces, sim)
    for i, tr in enumerate(ragged_traces):
        single = simulate(tr, sim)
        for k in SUMMARY_KEYS:
            np.testing.assert_allclose(
                np.asarray(out["summary"][k][i]),
                np.asarray(single["summary"][k]), rtol=1e-6, atol=1e-6,
                err_msg=f"{arch} summary[{k}] lane {i}")
        t = tr["ext_load"].shape[0]
        lat = np.asarray(out["records"]["latency"][i])
        np.testing.assert_allclose(
            lat[:t], np.asarray(single["records"]["latency"]),
            rtol=1e-6, atol=1e-6, err_msg=f"{arch} records lane {i}")
        # masked tail intervals record exactly zero everywhere
        for key in ("latency", "power_mw", "energy", "g", "wavelengths",
                    "reconfig_nj"):
            tail = np.asarray(out["records"][key][i], np.float32)[t:]
            assert np.all(tail == 0), \
                f"{arch} records[{key}] lane {i} nonzero past T={t}"
        assert not np.any(np.asarray(out["records"]["saturated"][i])[t:])


def test_ragged_batch_is_one_compile(ragged_traces):
    base = dataclasses.replace(SimConfig().with_arch(Arch.RESIPI),
                               prowaves_rho_lo=0.317)   # test-owned config
    reset_engine_stats()
    simulate_batch(ragged_traces, base)
    assert engine_stats()["simulate_traces"] == 1
    # warm re-call with different ragged lengths but same maxima: no retrace
    alt = [traffic.generate_trace("swaptions", t, jax.random.PRNGKey(9))
           for t in (21, 13, 7)]
    simulate_batch(alt, base)
    assert engine_stats()["simulate_traces"] == 1


def test_stack_traces_error_paths(ragged_traces):
    with pytest.raises(ValueError, match=r"mixed lengths T=\[21, 14, 9\]"):
        stack_traces(ragged_traces)
    with pytest.raises(ValueError, match="pad=True"):
        stack_traces(ragged_traces)
    with pytest.raises(ValueError, match="at least one"):
        stack_traces([])
    with pytest.raises(TypeError, match="trace dict"):
        stack_traces([jnp.zeros((4, 4))])
    wide = traffic.generate_trace(
        "dedup", 9, jax.random.PRNGKey(0), NETWORK.with_topology(n_chiplets=6))
    with pytest.raises(ValueError, match="chiplet counts"):
        stack_traces([ragged_traces[2], wide], pad=True)
    batch = stack_traces(ragged_traces, pad=True)
    assert batch["ext_load"].shape == (3, 21, NETWORK.n_chiplets)
    assert batch["t_mask"].shape == (3, 21)


def _stacking_inputs(case):
    """(traces, pad) for one `stack_traces` parity case."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    lengths = (12, 7, 10) if case == "ragged" else (12, 12, 12)
    traces = [traffic.generate(traffic.ParsecSpec(app=a, n_intervals=t), k,
                               dest=case == "dest")
              for a, t, k in zip(("dedup", "canneal", "facesim"), lengths,
                                 keys)]
    if case == "host":
        traces = jax.device_get(traces)
    elif case == "host_float64":
        traces = [{k: v if k == "app" else np.asarray(v, np.float64)
                   for k, v in tr.items()} for tr in traces]
    elif case == "mixed":
        traces[1] = jax.device_get(traces[1])
    elif case == "faults":
        traces = [faults.attach_faults(tr, faults.compile_faults(
            [faults.GatewayFault(start=i, chiplet=i, slot=0)], NETWORK, 12))
            for i, tr in enumerate(traces)]
    return traces, case == "ragged"


def _jnp_stacked(traces, pad):
    """`stack_traces`'s batch as `jnp.stack` builds it on the device."""
    t = max(int(jnp.shape(tr["ext_load"])[0]) for tr in traces)
    traces = [traffic.pad_trace(tr, t) for tr in traces] if pad else traces
    keys = [k for k in traces[0] if k != "app"]
    return {k: jnp.stack([jnp.asarray(tr[k]) for tr in traces])
            for k in keys}


@pytest.mark.parametrize("case", ["device", "host", "host_float64",
                                  "mixed", "ragged", "dest", "faults"])
def test_stack_traces_host_build_bit_matches_jnp_stack(case):
    """Concrete traces stack on the host: numpy arrays whose bytes and
    dtypes equal `jnp.stack` on the device."""
    traces, pad = _stacking_inputs(case)
    out = stack_traces(traces, pad=pad)
    ref = _jnp_stacked(traces, pad)
    assert set(out) - {"app"} == set(ref)
    assert out["app"] == ["dedup", "canneal", "facesim"]
    for k, want in ref.items():
        assert isinstance(out[k], np.ndarray), k
        assert out[k].dtype == want.dtype, k
        assert out[k].shape == want.shape, k
        assert out[k].tobytes() == np.asarray(want).tobytes(), k


def test_stack_traces_under_jit_stacks_tracers():
    traces, _ = _stacking_inputs("ragged")
    arrays = [{k: v for k, v in tr.items() if k != "app"} for tr in traces]

    def stacked(trs):
        out = stack_traces(trs, pad=True)
        assert isinstance(out["ext_load"], jax.core.Tracer)
        return {k: v for k, v in out.items() if k != "app"}

    out = jax.jit(stacked)(arrays)
    ref = _jnp_stacked(traces, True)
    assert set(out) == set(ref)
    for k, want in ref.items():
        assert isinstance(out[k], jax.Array), k
        assert out[k].dtype == want.dtype, k
        assert np.asarray(out[k]).tobytes() == np.asarray(want).tobytes(), k


def test_sweep_batch_over_host_built_batch_bit_matches_device_stack():
    sim = SimConfig()
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    traces = [traffic.generate(traffic.ParsecSpec(app=a, n_intervals=16), k)
              for a, k in zip(("blackscholes", "bodytrack"), keys)]
    grid = dict(l_m=np.asarray([0.008, 0.008, 0.02, 0.02], np.float32),
                wavelengths=np.asarray([2, 8, 2, 8], np.int32))
    got = sweep_batch(traces, sim, **grid)
    want = sweep_batch(_jnp_stacked(traces, True), sim, **grid)
    for part in ("summary", "records"):
        for k, v in want[part].items():
            assert np.asarray(got[part][k]).tobytes() \
                == np.asarray(v).tobytes(), f"{part}[{k}]"


@pytest.mark.parametrize("kind", ["device", "host"])
@pytest.mark.parametrize("bad", [np.nan, -1.0], ids=["nan", "negative"])
def test_stack_traces_still_validates_every_value(kind, bad):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    traces = [traffic.generate(traffic.ParsecSpec(app="dedup",
                                                  n_intervals=6), k)
              for k in keys]
    if kind == "host":
        traces = jax.device_get(traces)
    load = np.array(traces[1]["int_load"])
    load[3, 1] = bad
    traces[1] = dict(traces[1], int_load=(
        load if kind == "host" else jnp.asarray(load)))
    with pytest.raises(ValueError, match=r"traces\[1\]\['int_load'\]"):
        stack_traces(traces)


def test_padded_single_trace_through_simulate():
    """`simulate` itself honors a trace-carried t_mask."""
    sim = SimConfig().with_arch(Arch.RESIPI)
    tr = traffic.generate_trace("dedup", 12, jax.random.PRNGKey(3))
    padded = traffic.pad_trace(tr, 20)
    a = simulate(tr, sim)["summary"]
    b = simulate(padded, sim)["summary"]
    for k in SUMMARY_KEYS:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-6, atol=1e-6, err_msg=k)
    assert float(b["valid_intervals"]) == 12.0


def test_ragged_zips_with_topology_sweep():
    """t_mask rides the padded-topology executable too."""
    cfg = NETWORK.with_topology(n_chiplets=9)
    tr = traffic.generate_trace("dedup", 11, jax.random.PRNGKey(2), cfg)
    padded = traffic.pad_trace(tr, 16)
    base = SimConfig().with_arch(Arch.RESIPI)
    out = sweep_topology(padded, base, n_chiplets=[4, 9])
    for i, c in enumerate([4, 9]):
        single = simulate(traffic.slice_trace(tr, c),
                          topology_point_config(base, n_chiplets=c))
        np.testing.assert_allclose(
            np.asarray(out["summary"]["mean_latency"][i]),
            np.asarray(single["summary"]["mean_latency"]),
            rtol=1e-5, atol=1e-5, err_msg=f"topology point {i}")


# ---------------------------------------------------------------------------
# Workload sweeps
# ---------------------------------------------------------------------------

def test_sweep_workload_parity_and_one_compile():
    base = dataclasses.replace(SimConfig().with_arch(Arch.RESIPI),
                               prowaves_rho_lo=0.323)   # test-owned config
    specs = [traffic.ParsecSpec(app="dedup", n_intervals=12),
             traffic.UniformSpec(n_intervals=18),
             traffic.HotspotSpec(n_intervals=15),
             traffic.BurstySpec(n_intervals=10)]
    reset_engine_stats()
    out = sweep_workload(specs, base, seed=5)
    assert engine_stats()["simulate_traces"] == 1
    assert out["summary"]["mean_latency"].shape == (4,)
    keys = jax.random.split(jax.random.PRNGKey(5), len(specs))
    for i, (sp, ky) in enumerate(zip(specs, keys)):
        single = simulate(traffic.generate(sp, ky), base)
        for k in SUMMARY_KEYS:
            np.testing.assert_allclose(
                np.asarray(out["summary"][k][i]),
                np.asarray(single["summary"][k]), rtol=1e-6, atol=1e-6,
                err_msg=f"summary[{k}] workload {sp.name}")
    # warm re-call with fresh seed: same shapes, zero re-traces
    before = engine_stats()["simulate_traces"]
    sweep_workload(specs, base, seed=6)
    assert engine_stats()["simulate_traces"] == before


def test_sweep_workload_accepts_app_names_and_runtime_grids():
    base = SimConfig().with_arch(Arch.RESIPI)
    lms = [0.008, 0.02]
    out = sweep_workload(["dedup", "canneal"], base, seed=1,
                         l_m=jnp.asarray(lms))
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    for i, (app, lm) in enumerate(zip(["dedup", "canneal"], lms)):
        pinned = dataclasses.replace(base, ctl=dataclasses.replace(
            base.ctl, l_m=lm))
        single = simulate(traffic.generate(
            traffic.ParsecSpec(app=app), keys[i]), pinned)
        np.testing.assert_allclose(
            np.asarray(out["summary"]["mean_latency"][i]),
            np.asarray(single["summary"]["mean_latency"]),
            rtol=1e-5, atol=1e-5, err_msg=f"workload {app} l_m={lm}")


def test_sweep_workload_zips_with_topology():
    base = SimConfig().with_arch(Arch.RESIPI)
    specs = [traffic.UniformSpec(n_intervals=8),
             traffic.ParsecSpec(app="dedup", n_intervals=12)]
    cs = [4, 9]
    out = sweep_workload(specs, base, seed=2, n_chiplets=cs)
    gen_cfg = base.cfg.with_topology(n_chiplets=max(cs))
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    for i, (sp, c) in enumerate(zip(specs, cs)):
        tr = traffic.generate(sp, keys[i], gen_cfg)
        single = simulate(traffic.slice_trace(tr, c),
                          topology_point_config(base, n_chiplets=c))
        np.testing.assert_allclose(
            np.asarray(out["summary"]["mean_latency"][i]),
            np.asarray(single["summary"]["mean_latency"]),
            rtol=1e-5, atol=1e-5, err_msg=f"{sp.name} @ {c} chiplets")


def test_sweep_workload_validation():
    base = SimConfig().with_arch(Arch.RESIPI)
    with pytest.raises(ValueError, match="at least one"):
        sweep_workload([], base)
    with pytest.raises(ValueError, match="length 3 but 2"):
        sweep_workload(["dedup", "canneal"], base,
                       l_m=jnp.asarray([0.01, 0.02, 0.03]))
    with pytest.raises(ValueError, match="non-sweepable"):
        sweep_workload(["dedup"], base, bogus=jnp.asarray([1.0]))
    with pytest.raises(ValueError, match="2 keys for 1"):
        sweep_workload(["dedup"], base,
                       keys=jax.random.split(jax.random.PRNGKey(0), 2))
    # a bare scalar grid value gets a clear message, not a len() TypeError
    with pytest.raises(ValueError, match="1-D grid"):
        sweep_workload(["dedup"], base, l_m=0.015)
    with pytest.raises(ValueError, match="1-D grid"):
        sweep_topology(traffic.generate_trace(
            "dedup", 6, jax.random.PRNGKey(0)), base, n_chiplets=4)


def test_interior_mask_gap_freezes_state():
    """A mask-interior gap resumes exactly where the last valid interval
    left off: the controller must not react to the padded idle epochs
    (the frozen-carry contract, matching the noc_step kernel)."""
    for arch in (Arch.RESIPI, Arch.PROWAVES):
        sim = SimConfig().with_arch(arch)
        a = traffic.generate_trace("blackscholes", 9, jax.random.PRNGKey(0))
        b = traffic.generate_trace("facesim", 8, jax.random.PRNGKey(1))
        gapped = traffic.concat_traces([traffic.pad_trace(a, 14), b])
        plain = traffic.concat_traces([a, b])
        out_g = simulate(gapped, sim)
        out_p = simulate(plain, sim)
        for k in SUMMARY_KEYS:
            np.testing.assert_allclose(
                np.asarray(out_g["summary"][k]),
                np.asarray(out_p["summary"][k]), rtol=1e-6, atol=1e-6,
                err_msg=f"{arch} summary[{k}] with interior mask gap")
        # the b-segment records line up despite the 5 masked gap intervals
        np.testing.assert_allclose(
            np.asarray(out_g["records"]["latency"])[14:],
            np.asarray(out_p["records"]["latency"])[9:],
            rtol=1e-6, atol=1e-6, err_msg=f"{arch} post-gap records")


def test_midstream_padded_chunk_matches_oneshot():
    """Padding a NON-final chunk is exact too: the frozen carry lets a
    stream keep going after a padded chunk."""
    sim = SimConfig().with_arch(Arch.RESIPI)
    tr = traffic.generate_trace("canneal", 16, jax.random.PRNGKey(2))
    chunks = list(traffic.chunk_trace(tr, 8))
    session = SimSession.init(sim)
    session.step_chunk(traffic.pad_trace(chunks[0], 12))   # mid-stream pad
    session.step_chunk(chunks[1])
    one = simulate(tr, sim)["summary"]
    for k in SUMMARY_KEYS:
        np.testing.assert_allclose(
            np.asarray(session.summary()[k]), np.asarray(one[k]),
            rtol=1e-6, atol=1e-6, err_msg=k)
    assert session.intervals_seen == 16


# ---------------------------------------------------------------------------
# Streaming sessions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", list(Arch))
def test_session_bitmatches_oneshot_per_arch(arch):
    """Chunked records == one-shot records, bitwise, for every arch."""
    sim = SimConfig().with_arch(arch)
    tr = traffic.generate_trace("streamcluster", 24, jax.random.PRNGKey(4))
    one = simulate(tr, sim)
    session = SimSession.init(sim)
    chunk_recs = [session.step_chunk(ch)["records"]
                  for ch in _chunks(tr, 8)]
    cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *chunk_recs)
    for k in one["records"]:
        np.testing.assert_array_equal(
            np.asarray(cat[k]), np.asarray(one["records"][k]),
            err_msg=f"{arch} records[{k}] diverged across chunk boundary")
    assert session.intervals_seen == 24
    for k in SUMMARY_KEYS:
        np.testing.assert_allclose(
            np.asarray(session.summary()[k]),
            np.asarray(one["summary"][k]), rtol=1e-6, atol=1e-6,
            err_msg=f"{arch} summary[{k}]")


def test_session_steady_chunks_share_one_compile():
    sim = dataclasses.replace(SimConfig().with_arch(Arch.RESIPI),
                              prowaves_rho_lo=0.329)    # test-owned config
    tr = traffic.generate_trace("dedup", 40, jax.random.PRNGKey(6))
    session = SimSession.init(sim)
    reset_engine_stats()
    for ch in _chunks(tr, 10):
        session.step_chunk(ch)
    assert engine_stats()["simulate_traces"] == 1, \
        "equal-shape chunks must share one chunk executable"


def test_session_final_partial_chunk_via_padding():
    """A padded final chunk reuses the steady executable and stays exact."""
    sim = SimConfig().with_arch(Arch.RESIPI)
    tr = traffic.generate_trace("canneal", 22, jax.random.PRNGKey(8))
    one = simulate(tr, sim)["summary"]
    session = SimSession.init(sim)
    for ch in _chunks(tr, 8):                 # 8, 8, then ragged 6
        t = ch["ext_load"].shape[0]
        session.step_chunk(ch if t == 8 else traffic.pad_trace(ch, 8))
    for k in SUMMARY_KEYS:
        np.testing.assert_allclose(
            np.asarray(session.summary()[k]), np.asarray(one[k]),
            rtol=1e-6, atol=1e-6, err_msg=k)
    assert session.intervals_seen == 22


def test_simulate_stream_and_errors():
    sim = SimConfig().with_arch(Arch.RESIPI)
    tr = traffic.generate_trace("dedup", 16, jax.random.PRNGKey(1))
    out = simulate_stream(_chunks(tr, 4), sim)
    assert out["chunks"] == 4
    np.testing.assert_allclose(
        np.asarray(out["summary"]["mean_latency"]),
        np.asarray(simulate(tr, sim)["summary"]["mean_latency"]),
        rtol=1e-6)
    with pytest.raises(ValueError, match="empty chunk iterable"):
        simulate_stream([], sim)
    session = SimSession.init(sim)
    with pytest.raises(ValueError, match="before any step_chunk"):
        session.summary()
    with pytest.raises(ValueError, match="unbatched"):
        session.step_chunk(stack_traces([tr]))


# ---------------------------------------------------------------------------
# Kernel-level time mask
# ---------------------------------------------------------------------------

def test_noc_kernel_t_mask_freezes_tail():
    """Masked tail cycles == a shorter run, and they add zero residency."""
    nm, drain, buf, _ = build_topology(2, 4)
    n = nm.shape[0]
    arr = (jax.random.uniform(jax.random.PRNGKey(5), (192, n)) < 0.04
           ).astype(jnp.float32) * 8
    tm = (jnp.arange(192) < 100).astype(jnp.float32)
    rk, ok, dk = noc_run_pallas(
        arr, jnp.asarray(nm), jnp.asarray(drain), jnp.asarray(buf),
        t_mask=tm, t_chunk=64, interpret=True)
    rr, orr, dr = reference_noc_run(
        arr[:100], jnp.asarray(nm), jnp.asarray(drain), jnp.asarray(buf))
    np.testing.assert_allclose(np.asarray(rk), np.asarray(rr),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ok), np.asarray(orr),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                               rtol=1e-5, atol=1e-4)
    # ref with the same mask agrees with the kernel
    r2, o2, d2 = reference_noc_run(
        arr, jnp.asarray(nm), jnp.asarray(drain), jnp.asarray(buf),
        t_mask=tm)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(r2),
                               rtol=1e-5, atol=1e-4)


def test_noc_kernel_auto_time_padding():
    """T no longer needs to divide t_chunk: the tail pads as dead cycles."""
    nm, drain, buf, _ = build_topology(3, 4)
    n = nm.shape[0]
    arr = (jax.random.uniform(jax.random.PRNGKey(9), (100, n)) < 0.05
           ).astype(jnp.float32) * 8
    rk, ok, dk = noc_run_pallas(
        arr, jnp.asarray(nm), jnp.asarray(drain), jnp.asarray(buf),
        t_chunk=64, interpret=True)
    rr, orr, dr = reference_noc_run(
        arr, jnp.asarray(nm), jnp.asarray(drain), jnp.asarray(buf))
    np.testing.assert_allclose(np.asarray(rk), np.asarray(rr),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                               rtol=1e-5, atol=1e-4)
