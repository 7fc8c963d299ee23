"""One-dispatch Pareto co-design engine tests (PR 10).

Covers the device-resident archive (property: never holds a dominated
point; deterministic capacity eviction; numpy/device agreement), the
scalarization weights and hypervolume metric, the traced-topology twins
(`placement_tables_from_lut_jnp`, `_activation_order_mesh`) pinned
against their static-config originals, the one-dispatch `search_codesign`
engine (engine_stats accounting, determinism, host-oracle re-score
parity), the host engine invariants, and the pre-jit validation messages
for topology grids, knob grids and the islands axis.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pareto, topology, traffic
from repro.core.constants import NETWORK
from repro.core.gateway_controller import activation_order_jnp
from repro.core.selection import (placement_tables_from_lut_jnp,
                                  placement_tables_jnp)
from repro.core.simulator import (Arch, SimConfig, engine_stats,
                                  rescore_front_host, search_codesign,
                                  search_placement_islands)

MESHES = [(4, 4), (5, 5), (3, 6)]


# ---------------------------------------------------------------------------
# Archive properties
# ---------------------------------------------------------------------------

def _offer_np(batches, capacity, g=2):
    arch = pareto._empty_archive_np(capacity, g)
    for i, obj in enumerate(batches):
        n = len(obj)
        arch = pareto._archive_insert_np(
            arch, obj, np.zeros((n, g, 2), np.int32),
            np.full((n,), i, np.int32), np.arange(n, dtype=np.int32),
            capacity)
    return arch


def _assert_no_dominated(arch):
    obj = np.asarray(arch["obj"])
    valid = np.asarray(arch["valid"])
    rows = obj[valid]
    for i in range(len(rows)):
        for j in range(len(rows)):
            if i == j:
                continue
            dominated = (np.all(rows[j] <= rows[i])
                         and np.any(rows[j] < rows[i]))
            assert not dominated, (
                f"archive row {rows[i]} is dominated by {rows[j]}")


@pytest.mark.parametrize("seed", range(4))
def test_archive_never_holds_dominated_point(seed):
    rng = np.random.RandomState(seed)
    batches = [rng.uniform(0.1, 10.0, size=(rng.randint(1, 9), 3))
               .astype(np.float32) for _ in range(6)]
    for capacity in (4, 16, 64):
        _assert_no_dominated(_offer_np(batches, capacity))


@pytest.mark.parametrize("seed", range(3))
def test_archive_device_matches_numpy_mirror(seed):
    rng = np.random.RandomState(100 + seed)
    capacity, g = 8, 2
    arch_np = pareto._empty_archive_np(capacity, g)
    arch_dev = pareto._empty_archive(capacity, g)
    for i in range(4):
        obj = rng.uniform(0.1, 10.0, size=(5, 3)).astype(np.float32)
        pos = rng.randint(0, 4, size=(5, g, 2)).astype(np.int32)
        tix = np.full((5,), i, np.int32)
        kix = np.arange(5, dtype=np.int32)
        arch_np = pareto._archive_insert_np(arch_np, obj, pos, tix, kix,
                                            capacity)
        arch_dev = pareto._archive_insert(arch_dev, obj, pos, tix, kix,
                                          capacity=capacity)
    for k in ("obj", "pos", "topo", "island", "valid"):
        np.testing.assert_array_equal(np.asarray(arch_dev[k]), arch_np[k],
                                      err_msg=k)


def test_archive_dedup_keeps_earliest():
    obj = np.array([[1.0, 2.0, 3.0]], np.float32)
    arch = pareto._empty_archive_np(8, 2)
    arch = pareto._archive_insert_np(
        arch, obj, np.zeros((1, 2, 2), np.int32),
        np.array([7], np.int32), np.array([0], np.int32), 8)
    arch = pareto._archive_insert_np(
        arch, obj, np.ones((1, 2, 2), np.int32),
        np.array([9], np.int32), np.array([1], np.int32), 8)
    assert int(np.asarray(arch["valid"]).sum()) == 1
    assert int(arch["topo"][np.asarray(arch["valid"])][0]) == 7


def test_archive_capacity_eviction_deterministic():
    # 12 mutually non-dominated points (a 2-D staircase at constant z)
    # with distinct log-sum keys: eviction must keep exactly the capacity
    # best by ascending sum-of-log objectives, independent of insert order.
    n, capacity = 12, 5
    xs = np.arange(1, n + 1, dtype=np.float64)
    ys = 100.0 / xs**1.5                       # distinct products x*y
    pts = np.stack([xs, ys, np.full(n, 2.0)], axis=-1).astype(np.float32)
    key = np.log(np.maximum(pts.astype(np.float64), 1e-12)).sum(axis=1)
    expect = np.sort(key)[:capacity]

    for perm_seed in range(3):
        order = np.random.RandomState(perm_seed).permutation(n)
        arch = _offer_np([pts[order]], capacity)
        valid = np.asarray(arch["valid"])
        assert int(valid.sum()) == capacity
        got = np.sort(np.log(np.asarray(arch["obj"], np.float64)[valid])
                      .sum(axis=1))
        np.testing.assert_allclose(got, expect, rtol=1e-6)


def test_archive_rejects_nonfinite_candidates():
    obj = np.array([[1.0, np.inf, 3.0], [np.nan, 1.0, 1.0]], np.float32)
    arch = _offer_np([obj], 8)
    assert int(np.asarray(arch["valid"]).sum()) == 0


# ---------------------------------------------------------------------------
# Weights + hypervolume
# ---------------------------------------------------------------------------

def test_island_weights_simplex():
    for k in (1, 2, 3, 4, 8, 16):
        w = pareto.island_weights(k)
        assert w.shape == (k, 3)
        assert (w >= 0).all()
        np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-6)
        np.testing.assert_array_equal(w, pareto.island_weights(k))
    np.testing.assert_allclose(pareto.island_weights(1),
                               np.full((1, 3), 1 / 3), atol=1e-6)
    corners = {tuple(r) for r in pareto.island_weights(3).tolist()}
    assert corners == {(1.0, 0.0, 0.0), (0.0, 1.0, 0.0), (0.0, 0.0, 1.0)}
    with pytest.raises(ValueError, match="islands"):
        pareto.island_weights(0)


def test_hypervolume_known_values():
    ref = (2.0, 2.0, 2.0)
    assert pareto.hypervolume(np.empty((0, 3)), ref) == 0.0
    assert pareto.hypervolume([[1.0, 1.0, 1.0]], ref) == pytest.approx(1.0)
    # A dominated point adds nothing; a point outside the box is clipped.
    assert pareto.hypervolume([[1, 1, 1], [1.5, 1.5, 1.5]],
                              ref) == pytest.approx(1.0)
    assert pareto.hypervolume([[1, 1, 1], [3.0, 0.1, 0.1]],
                              ref) == pytest.approx(1.0)
    # Two non-dominated points, inclusion-exclusion: each dominates a
    # 4-volume box, overlapping in a 2-volume one.
    hv = pareto.hypervolume([[0.0, 1.0, 0.0], [1.0, 0.0, 0.0]], ref)
    assert hv == pytest.approx(4 + 4 - 2)


# ---------------------------------------------------------------------------
# Traced-topology twins vs their static-config originals
# ---------------------------------------------------------------------------

def _mesh_cfg(mx, my):
    return dataclasses.replace(NETWORK, mesh_x=mx, mesh_y=my,
                               gateway_positions=None)


def _random_placements(cfg, g, n, seed):
    rng = np.random.RandomState(seed)
    coords = np.asarray(topology.router_coords(cfg))
    return [coords[rng.choice(len(coords), size=g, replace=False)]
            for _ in range(n)]


def test_activation_order_mesh_matches_static_twin():
    a_bound = max(topology.centrality_bound(_mesh_cfg(mx, my))
                  for mx, my in MESHES)
    big_bound = 4 * max(mx + my for mx, my in MESHES)
    for mx, my in MESHES:
        cfg = _mesh_cfg(mx, my)
        for i, pos in enumerate(_random_placements(cfg, 4, 6, mx * 10 + my)):
            want = np.asarray(activation_order_jnp(pos, cfg))
            got = np.asarray(pareto._activation_order_mesh(
                jnp.asarray(pos), jnp.int32(mx), jnp.int32(my),
                a_bound=a_bound, big_bound=big_bound))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"mesh {mx}x{my} #{i}")


def test_placement_tables_from_lut_matches_static_twin():
    from repro.core.constants import PHOTONIC_POWER

    for mx, my in MESHES:
        cfg = _mesh_cfg(mx, my)
        g = cfg.max_gateways_per_chiplet
        hop_lut = jnp.asarray(topology.hop_lut(cfg))
        edge_lut = jnp.asarray(topology.edge_lut(cfg))
        mask = jnp.ones((cfg.routers_per_chiplet,), jnp.float32)
        caps = jnp.asarray([-(-cfg.routers_per_chiplet // k)
                            for k in range(1, g + 1)], jnp.int32)
        db_per_hop = float(cfg.router_pitch_mm
                           * PHOTONIC_POWER.waveguide_db_per_mm)
        for pos in _random_placements(cfg, g, 5, mx + my):
            want = placement_tables_jnp(jnp.asarray(pos), cfg)
            got = placement_tables_from_lut_jnp(
                jnp.asarray(pos), hop_lut, edge_lut, mask, caps,
                d_pad=topology.max_hops(cfg) + 1, db_per_hop=db_per_hop)
            for k in ("src_hops", "gw_loss_db"):
                np.testing.assert_allclose(np.asarray(got[k]),
                                           np.asarray(want[k]),
                                           rtol=0, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# The one-dispatch co-design search
# ---------------------------------------------------------------------------

CODESIGN_KW = dict(n_chiplets=[8, 16], mesh_radix=[4, 4], islands=2,
                   generations=3, population=3, archive=16,
                   knob_grids={"l_m": [0.01, 0.02]}, seed=1)


@pytest.fixture(scope="module")
def base():
    return SimConfig().with_arch(Arch.RESIPI)


@pytest.fixture(scope="module")
def traces(base):
    cfg16 = base.cfg.with_topology(n_chiplets=16)
    return [traffic.generate_trace(app, 6, jax.random.PRNGKey(i), cfg16)
            for i, app in enumerate(("dedup", "streamcluster"))]


@pytest.fixture(scope="module")
def device_run(traces, base):
    """One compiled co-design search + its dispatch-count delta."""
    before = engine_stats()["search_dispatches"]
    result = search_codesign(traces, base, **CODESIGN_KW)
    delta = engine_stats()["search_dispatches"] - before
    return result, delta


def test_codesign_is_one_dispatch(device_run):
    _, delta = device_run
    assert delta == 1


def test_codesign_front_invariants(device_run):
    result, _ = device_run
    assert result["engine"] == "device"
    assert result["islands"] == 2
    assert len(result["front"]) >= 1
    objs = np.array([[e["objectives"][k]
                      for k in ("latency", "power_mw", "energy")]
                     for e in result["front"]])
    assert np.isfinite(objs).all() and (objs > 0).all()
    _assert_no_dominated({"obj": objs,
                          "valid": np.ones(len(objs), bool)})
    for e in result["front"]:
        t = e["topology_index"]
        assert e["topology"]["n_chiplets"] == CODESIGN_KW["n_chiplets"][t]
        assert len(set(e["placement"])) == len(e["placement"])
        assert e["knobs"]["l_m"] == pytest.approx(
            CODESIGN_KW["knob_grids"]["l_m"][e["island"]])
    hist = result["history"]["archive_size"]
    assert hist.shape == (2, CODESIGN_KW["generations"])
    assert np.isfinite(result["history"]["best_scalar"]).all()
    # T * generations * islands * population * workloads
    assert result["candidate_evals"] == (
        2 * CODESIGN_KW["generations"] * CODESIGN_KW["islands"]
        * CODESIGN_KW["population"] * 2)


def test_codesign_front_matches_host_rescore(device_run, traces, base):
    result, _ = device_run
    got = np.array([[e["objectives"][k]
                     for k in ("latency", "power_mw", "energy")]
                    for e in result["front"]])
    want = rescore_front_host(result, traces, base)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_codesign_deterministic(device_run, traces, base):
    result, _ = device_run
    again = search_codesign(traces, base, **CODESIGN_KW)
    assert [e["placement"] for e in again["front"]] == \
        [e["placement"] for e in result["front"]]
    np.testing.assert_array_equal(
        np.array([e["objectives"]["latency"] for e in result["front"]]),
        np.array([e["objectives"]["latency"] for e in again["front"]]))


# ---------------------------------------------------------------------------
# Host engine (parity oracle)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def host_run(traces, base):
    return search_codesign(traces, base, engine="host", n_chiplets=[8, 16],
                           mesh_radix=[4, 4], islands=2, generations=2,
                           population=3, archive=16,
                           knob_grids={"l_m": [0.01, 0.02]}, seed=1)


def test_host_engine_invariants(host_run):
    assert host_run["engine"] == "host"
    assert len(host_run["front"]) >= 1
    objs = np.array([[e["objectives"][k]
                      for k in ("latency", "power_mw", "energy")]
                     for e in host_run["front"]])
    _assert_no_dominated({"obj": objs,
                          "valid": np.ones(len(objs), bool)})


def test_host_engine_self_rescore_exact(host_run, traces, base):
    got = np.array([[e["objectives"][k]
                     for k in ("latency", "power_mw", "energy")]
                    for e in host_run["front"]])
    want = rescore_front_host(host_run, traces, base)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_host_engine_deterministic(host_run, traces, base):
    again = search_codesign(traces, base, engine="host",
                            n_chiplets=[8, 16], mesh_radix=[4, 4],
                            islands=2, generations=2, population=3,
                            archive=16, knob_grids={"l_m": [0.01, 0.02]},
                            seed=1)
    assert [e["placement"] for e in again["front"]] == \
        [e["placement"] for e in host_run["front"]]


# ---------------------------------------------------------------------------
# Pre-jit validation
# ---------------------------------------------------------------------------

def test_codesign_rejects_gateway_positions_grid(base):
    with pytest.raises(ValueError, match="not a co-design axis"):
        search_codesign(None, base, gateway_positions=[None])


def test_codesign_routes_runtime_fields_to_knob_grids(base):
    with pytest.raises(ValueError, match="knob_grids"):
        search_codesign(None, base, l_m=[0.01])


def test_codesign_rejects_unknown_topology_field(base):
    with pytest.raises(ValueError, match="non-sweepable"):
        search_codesign(None, base, bogus=[1, 2])


def test_codesign_rejects_varying_gateway_width(base):
    with pytest.raises(ValueError, match="must be constant"):
        search_codesign(None, base, n_chiplets=[8, 8],
                        gateways_per_chiplet=[2, 4])


def test_codesign_rejects_knob_length_mismatch(base):
    with pytest.raises(ValueError, match="islands=3"):
        search_codesign(None, base, islands=3,
                        knob_grids={"l_m": [0.01, 0.02]})


def test_codesign_rejects_topology_field_in_knobs(base):
    with pytest.raises(ValueError, match="grid axes"):
        search_codesign(None, base, knob_grids={"n_chiplets": [8, 16]})


def test_codesign_rejects_non_integer_islands(base):
    with pytest.raises(ValueError, match="islands must be an int"):
        search_codesign(None, base, islands=2.5)


def test_codesign_rejects_islands_not_dividing_devices(base):
    """Explicit devices are a sharding request: an island count that does
    not divide over them raises instead of quietly running on one."""
    devices = jax.devices()[:1] * 2
    with pytest.raises(ValueError, match="does not divide over 2 devices"):
        search_codesign(None, base, islands=3, devices=devices)


def test_codesign_rejects_unknown_engine(base):
    with pytest.raises(ValueError, match="unknown engine"):
        search_codesign(None, base, engine="magic")


def test_codesign_rejects_explicit_coords_config(base):
    hex_sim = dataclasses.replace(base, cfg=topology.hex_config(2))
    with pytest.raises(ValueError, match="derived-mesh"):
        search_codesign(None, hex_sim, n_chiplets=[8])


@pytest.fixture(scope="module")
def small_trace(base):
    return traffic.generate_trace("dedup", 4, jax.random.PRNGKey(3),
                                  base.cfg)


def test_islands_rejects_non_integer_islands(small_trace, base):
    with pytest.raises(ValueError, match="islands must be an int"):
        search_placement_islands(small_trace, base, islands=2.5)


def test_islands_rejects_non_numeric_grid(small_trace, base):
    with pytest.raises(ValueError, match="numeric grid"):
        search_placement_islands(small_trace, base, islands=2,
                                 l_m=["a", "b"])
