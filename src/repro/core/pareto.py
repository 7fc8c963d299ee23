"""One-dispatch Pareto co-design engine (PR 10).

ReSiPI's design space is three-axed: the interposer *topology* (chiplet
count, per-chiplet gateway budget, intra-chiplet mesh radix), the gateway
*placement* on each chiplet's router mesh, and the controller's runtime
*knobs* (L_m thresholds, wavelength budget, gateway bounds). PR 4 swept
topology shapes in one padded executable; PR 5 moved the annealed
placement search on-device. This module closes the loop: a joint
topology x placement x knob search whose ENTIRE trajectory — an outer
`lax.scan` over padded topology grid points, the PR-5 annealed island
chains inside each, periodic ring migration of island incumbents, and a
device-resident Pareto archive over (latency, power, energy) — is ONE
compiled dispatch (`engine_stats()["search_dispatches"]` counts exactly
one launch per `search_codesign`, and the only device->host transfer is
the final result pytree).

Multi-objective mechanics, all on device:

  * Each of the K islands carries a fixed scalarization weight vector
    (`island_weights`, a Das-Dennis-style simplex spread), normalized per
    topology point by its generation-0 default-placement objectives, so
    the K annealed chains climb toward *different* regions of the front.
  * Every (island, candidate) scored anywhere in the search is offered to
    a fixed-capacity archive carried through both scans: a vectorized
    dominance + duplicate mask keeps only non-dominated points, and
    capacity eviction is deterministic (ascending sum-of-log objectives,
    ties by insertion index). The archive spans ALL topology points —
    dominance is global, so the returned front is the co-design answer,
    not a per-topology best.
  * Every `migrate_every` generations each island adopts its ring
    neighbor's incumbent (island k inherits island k-1's best placement),
    so good placements discovered under one weight vector seed the
    neighboring objective trade-offs.

The topology axes ride the PR-4 padding scheme (chiplet/router axes at
grid maxima, per-point validity masks); candidate placement tables are
built by `selection.placement_tables_from_lut_jnp`, the traced-topology
twin whose hop/edge LUTs arrive as scan inputs instead of static config.
`engine="host"` runs the same searcher semantics as a host-driven loop
over the public `sweep_topology_batch` machinery (the parity oracle:
different PRNG streams, identical scoring path), and
`rescore_front_host` re-scores a device front through that public path
for the 1e-6 device==host parity check.

Derived-mesh grids only: explicit-coords layouts (hex) fix the topology,
so their placement search is `search_placement_islands` on that config.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constants import PHOTONIC_POWER
from repro.core.search import _one_move
from repro.core.selection import (N_DEFAULT_EDGE_SLOTS, normalize_placement,
                                  placement_tables_from_lut_jnp,
                                  resolve_gateway_positions)
# Cycle-free for the same reason repro.core.search's import is: simulator
# re-exports this module's entry points lazily, never at module top.
from repro.core.simulator import (SWEEPABLE_FIELDS, TOPOLOGY_SWEEPABLE_FIELDS,
                                  stack_traces)

# Objective vector order — columns of every [.., 3] objectives array.
PARETO_OBJECTIVES = ("mean_latency", "mean_power_mw", "mean_energy")

# Topology axes the co-design grid accepts (placements are *searched*, so
# the gateway_positions sweep axis is deliberately absent).
CODESIGN_TOPOLOGY_FIELDS = ("n_chiplets", "gateways_per_chiplet",
                            "mesh_radix")

# Per-(topology, generation) history row layout.
CODESIGN_HISTORY_KEYS = ("archive_size", "best_scalar")


def island_weights(islands: int) -> np.ndarray:
    """[K, 3] deterministic scalarization weights spread over the simplex.

    Das-Dennis construction: the smallest simplex-lattice layer with at
    least K points, enumerated lexicographically, subsampled at evenly
    spaced indices — so K=3 gives the pure corners (one island per single
    objective) and larger K fills the interior trade-offs. K=1 uses the
    uniform weight (balanced compromise search).
    """
    if islands < 1:
        raise ValueError("islands must be >= 1")
    if islands == 1:
        return np.full((1, 3), 1.0 / 3.0, np.float32)
    h = 1
    while (h + 1) * (h + 2) // 2 < islands:
        h += 1
    pts = [(i, j, h - i - j)
           for i in range(h + 1) for j in range(h + 1 - i)]
    idx = np.round(np.linspace(0, len(pts) - 1, islands)).astype(int)
    return np.asarray([pts[i] for i in idx], np.float32) / float(h)


# ---------------------------------------------------------------------------
# Device-resident Pareto archive
# ---------------------------------------------------------------------------

def _empty_archive(capacity: int, g: int) -> dict:
    return {"obj": jnp.full((capacity, 3), jnp.inf, jnp.float32),
            "pos": jnp.zeros((capacity, g, 2), jnp.int32),
            "topo": jnp.full((capacity,), -1, jnp.int32),
            "island": jnp.full((capacity,), -1, jnp.int32),
            "valid": jnp.zeros((capacity,), bool)}


def _archive_insert(arch: dict, cobj, cpos, ctopo, cisland, *,
                    capacity: int) -> dict:
    """Offer a candidate batch to the archive (traced, fixed shapes).

    Vectorized dominance: row i eliminates row j when i's objectives are
    <= everywhere and < somewhere, or when the rows are equal and i was
    inserted earlier (duplicate dedup). Capacity eviction sorts survivors
    by ascending sum-of-log objectives (a geometric-mean quality proxy),
    stable, ties by index — fully deterministic, no RNG. The archive can
    therefore evict genuinely non-dominated points once the front exceeds
    `capacity`; what it NEVER holds is a dominated one (property-tested).
    """
    obj = jnp.concatenate([arch["obj"], jnp.asarray(cobj, jnp.float32)])
    pos = jnp.concatenate([arch["pos"], jnp.asarray(cpos, jnp.int32)])
    tix = jnp.concatenate([arch["topo"], jnp.asarray(ctopo, jnp.int32)])
    kix = jnp.concatenate([arch["island"], jnp.asarray(cisland, jnp.int32)])
    cvalid = jnp.all(jnp.isfinite(jnp.asarray(cobj, jnp.float32)), axis=1)
    valid = jnp.concatenate([arch["valid"], cvalid])

    idx = jnp.arange(obj.shape[0])
    both = valid[:, None] & valid[None, :]
    le = jnp.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
    lt = jnp.any(obj[:, None, :] < obj[None, :, :], axis=-1)
    beaten = jnp.any(both & le & (lt | (idx[:, None] < idx[None, :])),
                     axis=0)
    keep = valid & ~beaten
    key = jnp.where(keep,
                    jnp.sum(jnp.log(jnp.maximum(obj, 1e-12)), axis=-1),
                    jnp.inf)
    top = jnp.argsort(key)[:capacity]
    kt = keep[top]
    return {"obj": jnp.where(kt[:, None], obj[top], jnp.inf),
            "pos": pos[top],
            "topo": jnp.where(kt, tix[top], -1),
            "island": jnp.where(kt, kix[top], -1),
            "valid": kt}


def _archive_insert_np(arch: dict, cobj, cpos, ctopo, cisland,
                       capacity: int) -> dict:
    """Numpy mirror of `_archive_insert` (host engine + property tests)."""
    obj = np.concatenate([arch["obj"], np.asarray(cobj, np.float32)])
    pos = np.concatenate([arch["pos"], np.asarray(cpos, np.int32)])
    tix = np.concatenate([arch["topo"], np.asarray(ctopo, np.int32)])
    kix = np.concatenate([arch["island"], np.asarray(cisland, np.int32)])
    cvalid = np.all(np.isfinite(np.asarray(cobj, np.float32)), axis=1)
    valid = np.concatenate([arch["valid"], cvalid])

    idx = np.arange(obj.shape[0])
    both = valid[:, None] & valid[None, :]
    le = np.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
    lt = np.any(obj[:, None, :] < obj[None, :, :], axis=-1)
    beaten = np.any(both & le & (lt | (idx[:, None] < idx[None, :])),
                    axis=0)
    keep = valid & ~beaten
    key = np.where(keep,
                   np.sum(np.log(np.maximum(obj, 1e-12)), axis=-1),
                   np.inf)
    top = np.argsort(key, kind="stable")[:capacity]
    kt = keep[top]
    return {"obj": np.where(kt[:, None], obj[top], np.inf),
            "pos": pos[top],
            "topo": np.where(kt, tix[top], -1),
            "island": np.where(kt, kix[top], -1),
            "valid": kt}


def _empty_archive_np(capacity: int, g: int) -> dict:
    return {"obj": np.full((capacity, 3), np.inf, np.float32),
            "pos": np.zeros((capacity, g, 2), np.int32),
            "topo": np.full((capacity,), -1, np.int32),
            "island": np.full((capacity,), -1, np.int32),
            "valid": np.zeros((capacity,), bool)}


def hypervolume(points, ref) -> float:
    """Dominated 3-D hypervolume of a minimization front w.r.t. `ref`.

    Host-side numpy (bench metric): slice the volume along the third
    objective and accumulate 2-D staircase areas — exact for any front
    size the archive can hold. Points outside the reference box are
    clipped away (they contribute nothing).
    """
    pts = np.asarray(points, np.float64).reshape(-1, 3)
    ref = np.asarray(ref, np.float64).reshape(3)
    pts = pts[np.all(np.isfinite(pts), axis=1)]
    pts = pts[np.all(pts < ref, axis=1)]
    if pts.shape[0] == 0:
        return 0.0
    pts = np.unique(pts, axis=0)
    keep = [i for i in range(len(pts))
            if not any(np.all(pts[j] <= pts[i]) and np.any(pts[j] < pts[i])
                       for j in range(len(pts)) if j != i)]
    pts = pts[keep]

    def area2d(xy):
        if xy.shape[0] == 0:
            return 0.0
        xy = xy[np.argsort(xy[:, 0], kind="stable")]
        area, y_best = 0.0, ref[1]
        for x, y in xy:
            if y < y_best:
                area += (ref[0] - x) * (y_best - y)
                y_best = y
        return area

    zs = np.unique(pts[:, 2])
    hv = 0.0
    for i, z in enumerate(zs):
        z_next = zs[i + 1] if i + 1 < len(zs) else ref[2]
        hv += area2d(pts[pts[:, 2] <= z, :2]) * (z_next - z)
    return float(hv)


# ---------------------------------------------------------------------------
# Traced-topology activation order (mesh rule with traced radix)
# ---------------------------------------------------------------------------

def _activation_order_mesh(pos, mx, my, *, a_bound: int,
                           big_bound: int) -> jax.Array:
    """`activation_order_jnp`'s mesh rule with the radix as traced data.

    `mx`/`my` are per-topology-point scalars riding the co-design scan;
    `a_bound`/`big_bound` are static grid-maximum bounds. The composite
    integer keys order identically for any bound >= the per-point exact
    one (the tie-break terms stay strictly below `a`), so the result
    matches `activation_order_jnp(pos, cfg_t)` per point exactly — pinned
    in tests/test_pareto.py.
    """
    pos = jnp.asarray(pos, jnp.int32).reshape(-1, 2)
    n = int(pos.shape[0])
    idx = jnp.arange(n, dtype=jnp.int32)
    cent2 = (jnp.abs(2 * pos[:, 0] - (mx - 1))
             + jnp.abs(2 * pos[:, 1] - (my - 1)))
    pair = jnp.sum(jnp.abs(pos[:, None, :] - pos[None, :, :]), axis=-1)
    big = jnp.int32(big_bound)
    b = n
    a = int(a_bound) * b
    taken = jnp.iinfo(jnp.int32).max

    first = jnp.argmin(cent2 * b + idx).astype(jnp.int32)
    order = jnp.zeros((n,), jnp.int32).at[0].set(first)
    selected = idx == first
    for k in range(1, n):
        dmin = jnp.min(jnp.where(selected[None, :], pair, big), axis=1)
        key = jnp.where(selected, taken, -dmin * a + cent2 * b + idx)
        nxt = jnp.argmin(key).astype(jnp.int32)
        order = order.at[k].set(nxt)
        selected = selected | (idx == nxt)
    return order


# ---------------------------------------------------------------------------
# The one-dispatch co-design core
# ---------------------------------------------------------------------------

def _codesign_core(key, topo, ov, weights, hyper, ext, mem, intra, ext_frac,
                   t_mask, dest, *, sim, generations: int, population: int,
                   migrate_every: int, archive: int, d_pad: int,
                   db_per_hop: float, moves_hi: int, a_bound: int,
                   big_bound: int) -> dict:
    """Outer scan over topology points, inner scan over generations.

    All randomness is pre-drawn in a handful of vectorized calls (the
    PR-5 lesson: threefry-per-draw inside the scan dominates CPU cost);
    the scan bodies do only arithmetic. `sim.cfg` is the PADDED shape
    (grid maxima); everything per-point arrives in `topo` as [T, ...]
    stacks consumed as outer-scan xs.
    """
    from repro.core import simulator as _sim

    g = sim.cfg.max_gateways_per_chiplet
    t_pts = int(topo["n_chiplets"].shape[0])
    k_isl = int(weights.shape[0])
    n_prop = population - 1
    r_pad = int(topo["coords"].shape[1])

    k_flag, k_perm, k_idx, k_gum, k_acc = jax.random.split(key, 5)
    restart = jax.random.bernoulli(
        k_flag, hyper["restart_frac"],
        (t_pts, generations, k_isl, n_prop))
    rest_gum = jax.random.gumbel(
        k_perm, (t_pts, generations, k_isl, n_prop, r_pad))
    move_i = jax.random.randint(
        k_idx, (t_pts, generations, k_isl, n_prop, 2), 0, g)
    move_gum = jax.random.gumbel(
        k_gum, (t_pts, generations, k_isl, n_prop, 2, r_pad))
    acc_u = jax.random.uniform(k_acc, (t_pts, generations, k_isl))

    def topo_body(arch, xs):
        tp, rst, rgum, mvi, mvg, u_all, t_idx = xs
        coords_t = tp["coords"]
        blocked_t = tp["blocked"]
        # Gumbel-top-g over the real routers = uniform g-subset without
        # replacement (restart placements, same construction as PR 5).
        gum = jnp.where(blocked_t[None, None, None, :] > 0.5, -jnp.inf,
                        rgum)
        _, ridx = jax.lax.top_k(gum, g)
        rpos = coords_t[ridx]            # [GEN, K, n_prop, g, 2]

        # Controller gateway bounds clamp to this point's gateway count —
        # the same per-point clamp sweep_topology applies on the host.
        maxg = jnp.minimum(ov["max_gateways"].astype(jnp.int32),
                           tp["g_max"])
        ming = jnp.minimum(ov["min_gateways"].astype(jnp.int32), maxg)
        ov_t = dict(ov, max_gateways=maxg, min_gateways=ming)
        topo_base = {"n_chiplets": tp["n_chiplets"], "g_max": tp["g_max"],
                     "mesh_hops": tp["mesh_hops"], "mesh_x": tp["feed"],
                     "total_gateways": tp["total_gateways"]}
        parent0 = jnp.broadcast_to(tp["default_pos"][None],
                                   (k_isl, g, 2)).astype(jnp.int32)

        def spread(p):
            return p[_activation_order_mesh(p, tp["mx"], tp["my"],
                                            a_bound=a_bound,
                                            big_bound=big_bound)]

        def gen_body(c, xs_g):
            gen, rst_g, rpos_g, mvi_g, mvg_g, u = xs_g
            parent = c["parent"]
            if migrate_every > 0:
                # Ring migration: island k adopts island k-1's incumbent.
                do_mig = (gen > 0) & (gen % migrate_every == 0)
                parent = jnp.where(do_mig,
                                   jnp.roll(c["inc_pos"], 1, axis=0),
                                   parent)
            moves = jnp.where(gen < moves_hi, 2, 1)

            def prop_one(par, r, rp, mi, mg):
                m1 = _one_move(par, mi[0], mg[0], coords_t, blocked_t)
                m2 = _one_move(m1, mi[1], mg[1], coords_t, blocked_t)
                return spread(jnp.where(r, rp,
                                        jnp.where(moves > 1, m2, m1)))

            props = jax.vmap(lambda par, r, rp, mi, mg: jax.vmap(
                functools.partial(prop_one, par))(r, rp, mi, mg))(
                    parent, rst_g, rpos_g, mvi_g, mvg_g)
            cands = jnp.concatenate([parent[:, None], props], axis=1)

            tbls = jax.vmap(jax.vmap(
                lambda p: placement_tables_from_lut_jnp(
                    p, tp["hop_lut"], tp["edge_lut"], tp["router_mask"],
                    tp["caps"], d_pad=d_pad, db_per_hop=db_per_hop)
            ))(cands)

            def score_one(tbl, o):
                tc = dict(topo_base, src_hops=tbl["src_hops"],
                          gw_loss_db=tbl["gw_loss_db"])

                def one_w(e, m, i, f, t, d):
                    out = _sim._simulate_impl(e, m, i, f, t, sim, None, o,
                                              topo=tc, dest=d)
                    return jnp.stack([out["summary"][x]
                                      for x in PARETO_OBJECTIVES])

                per_w = jax.vmap(one_w)(ext, mem, intra, ext_frac, t_mask,
                                        dest)
                return jnp.mean(per_w, axis=0)

            objs = jax.vmap(lambda tb, o: jax.vmap(
                lambda t1: score_one(t1, o))(tb))(tbls, ov_t)   # [K, P, 3]

            # Per-island normalization: this point's generation-0 parent
            # (the default placement) anchors the scalarization scale.
            norm = jnp.where(gen == 0, objs[:, 0, :], c["norm"])
            denom = jnp.maximum(jnp.abs(norm), 1e-12)
            s = jnp.sum(weights[:, None, :] * objs / denom[:, None, :],
                        axis=-1)                                 # [K, P]

            ib = jnp.argmin(s, axis=1)
            sb = jnp.take_along_axis(s, ib[:, None], axis=1)[:, 0]
            cb = jnp.take_along_axis(
                cands, ib[:, None, None, None], axis=1)[:, 0]
            improved = sb < c["inc_s"]
            inc_pos = jnp.where(improved[:, None, None], cb, c["inc_pos"])
            inc_s = jnp.minimum(sb, c["inc_s"])

            # Annealed metropolis per island (host-engine law).
            delta = sb - s[:, 0]
            rel = delta / jnp.maximum(jnp.abs(s[:, 0]), 1e-12)
            temp = (hyper["temperature"]
                    * hyper["cooling"] ** gen.astype(jnp.float32))
            metropolis = (temp > 0) & (
                u < jnp.exp(-rel / jnp.maximum(temp, 1e-30)))
            accepted = (delta < 0) | metropolis
            parent = jnp.where(accepted[:, None, None], cb, parent)

            arch_new = _archive_insert(
                c["arch"], objs.reshape(-1, 3),
                cands.reshape(-1, g, 2),
                jnp.full((k_isl * population,), t_idx, jnp.int32),
                jnp.repeat(jnp.arange(k_isl, dtype=jnp.int32), population),
                capacity=archive)
            rec = jnp.stack([jnp.sum(arch_new["valid"].astype(jnp.float32)),
                             jnp.min(inc_s)])
            return {"parent": parent, "inc_pos": inc_pos, "inc_s": inc_s,
                    "norm": norm, "arch": arch_new}, rec

        c0 = {"parent": parent0, "inc_pos": parent0,
              "inc_s": jnp.full((k_isl,), jnp.inf, jnp.float32),
              "norm": jnp.ones((k_isl, 3), jnp.float32), "arch": arch}
        cend, hist = jax.lax.scan(
            gen_body, c0,
            (jnp.arange(generations, dtype=jnp.int32), rst, rpos, mvi, mvg,
             u_all))
        return cend["arch"], (hist, cend["inc_pos"], cend["inc_s"])

    arch0 = _empty_archive(archive, g)
    arch_fin, (hist, inc_pos, inc_s) = jax.lax.scan(
        topo_body, arch0,
        (topo, restart, rest_gum, move_i, move_gum, acc_u,
         jnp.arange(t_pts, dtype=jnp.int32)))
    return {"archive": arch_fin, "history": hist,
            "island_incumbents": inc_pos, "island_scores": inc_s}

_CODESIGN_STATICS = ("sim", "generations", "population", "migrate_every",
                     "archive", "d_pad", "db_per_hop", "moves_hi",
                     "a_bound", "big_bound")


@functools.partial(jax.jit, static_argnames=_CODESIGN_STATICS)
def _codesign_jit(key, topo, ov, weights, hyper, ext, mem, intra, ext_frac,
                  t_mask, dest=None, *, sim, generations, population,
                  migrate_every, archive, d_pad, db_per_hop, moves_hi,
                  a_bound, big_bound):
    return _codesign_core(key, topo, ov, weights, hyper, ext, mem, intra,
                          ext_frac, t_mask, dest, sim=sim,
                          generations=generations, population=population,
                          migrate_every=migrate_every, archive=archive,
                          d_pad=d_pad, db_per_hop=db_per_hop,
                          moves_hi=moves_hi, a_bound=a_bound,
                          big_bound=big_bound)


def clear_codesign_caches() -> None:
    """Drop the compiled co-design executables (cold-start measurement)."""
    _codesign_jit.clear_cache()


# ---------------------------------------------------------------------------
# Grid validation + host-side preparation
# ---------------------------------------------------------------------------

def _check_codesign_params(generations, population, migrate_every,
                           archive) -> None:
    if population < 2:
        raise ValueError("population must be >= 2 (incumbent + candidates)")
    if generations < 1:
        raise ValueError("generations must be >= 1")
    if migrate_every < 0:
        raise ValueError("migrate_every must be >= 0 (0 disables migration)")
    if archive < 1:
        raise ValueError("archive must be >= 1")


def _check_topology_grids(sim, topo_grids: dict):
    """Pre-jit topology-axis validation with actionable messages.

    Returns (cs, gs, rs) integer lists of one shared length T (T=1 for an
    empty grid: placement x knob search on the base topology).
    """
    from repro.core import simulator as _sim

    cfg = sim.cfg
    if cfg.coords is not None:
        raise ValueError(
            "search_codesign sweeps derived-mesh topology grids; explicit-"
            "coords layouts (NetworkConfig.coords) fix the topology — "
            "search placements there with search_placement_islands")
    if "gateway_positions" in topo_grids:
        raise ValueError(
            "gateway_positions is not a co-design axis: placements are "
            "SEARCHED per topology point, not swept (pin one with "
            "sweep_topology instead)")
    unknown = set(topo_grids) - set(CODESIGN_TOPOLOGY_FIELDS)
    runtime = unknown & set(SWEEPABLE_FIELDS)
    if runtime:
        raise ValueError(
            f"runtime fields {sorted(runtime)} zip with the island axis — "
            f"pass them via knob_grids={{field: [K values]}}, not as "
            f"topology grids")
    if unknown:
        raise ValueError(
            f"non-sweepable fields: {sorted(unknown)} (co-design topology "
            f"axes: {CODESIGN_TOPOLOGY_FIELDS}; runtime knobs ride "
            f"knob_grids)")
    lengths = {k: _sim._grid_len(k, v) for k, v in topo_grids.items()}
    if lengths and len(set(lengths.values())) != 1:
        raise ValueError(
            f"topology grids must share one length, got {lengths}")
    t_pts = next(iter(lengths.values())) if lengths else 1
    cs = [int(x) for x in topo_grids.get("n_chiplets",
                                         [cfg.n_chiplets] * t_pts)]
    gs = [int(x) for x in topo_grids.get(
        "gateways_per_chiplet", [cfg.max_gateways_per_chiplet] * t_pts)]
    rs = [int(x) for x in topo_grids.get("mesh_radix",
                                         [cfg.mesh_x] * t_pts)]
    if min(cs) < 1 or min(gs) < 1 or min(rs) < 2:
        raise ValueError(f"invalid topology grid: n_chiplets {cs}, "
                         f"gateways {gs}, radix {rs}")
    if len(set(gs)) != 1:
        raise ValueError(
            f"gateways_per_chiplet must be constant across a co-design "
            f"grid (got {gs}): the placement axis is [g, 2] per candidate "
            f"and cannot change width mid-scan — trade gateway counts at "
            f"runtime with knob_grids={{'max_gateways': [...]}} instead")
    g = gs[0]
    if g > N_DEFAULT_EDGE_SLOTS:
        raise ValueError(
            f"gateways_per_chiplet={g} exceeds the {N_DEFAULT_EDGE_SLOTS} "
            f"default edge slots that seed the search")
    for i, r in enumerate(rs):
        if g > r * r:
            raise ValueError(
                f"grid point {i}: gateways_per_chiplet={g} exceeds the "
                f"{r}x{r} mesh's {r * r} routers")
    return cs, gs, rs


def _check_knob_grids(knob_grids, islands):
    """Pre-jit knob validation. Returns (knobs dict of lists, islands)."""
    from repro.core import simulator as _sim

    if islands is not None and (isinstance(islands, bool)
                                or not isinstance(islands,
                                                  (int, np.integer))):
        raise ValueError(
            f"islands must be an int, got {type(islands).__name__} "
            f"{islands!r}")
    knobs = dict(knob_grids or {})
    unknown = set(knobs) - set(SWEEPABLE_FIELDS)
    if unknown:
        topo = unknown & set(TOPOLOGY_SWEEPABLE_FIELDS)
        if topo:
            raise ValueError(
                f"topology fields {sorted(topo)} are grid axes, not island "
                f"knobs — pass them as keyword grids "
                f"(search_codesign(tr, sim, n_chiplets=[...]))")
        raise ValueError(
            f"non-sweepable knob fields: {sorted(unknown)} (runtime knobs: "
            f"{SWEEPABLE_FIELDS})")
    lengths = {f: _sim._grid_len(f, v) for f, v in knobs.items()}
    if islands is None:
        if lengths:
            if len(set(lengths.values())) != 1:
                raise ValueError(
                    f"knob grids must share one length, got {lengths}")
            islands = next(iter(lengths.values()))
        else:
            islands = 8
    bad = {f: n for f, n in lengths.items() if n != islands}
    if bad:
        raise ValueError(
            f"knob grids must have length islands={islands}, got {bad} — "
            f"every knob grid zips element-wise with the island axis")
    if islands < 1:
        raise ValueError("islands must be >= 1")
    return {f: list(np.asarray(v).tolist()) for f, v in knobs.items()}, \
        int(islands)


def _prepare_codesign(sim, cs, gs, rs):
    """Padded per-topology stacks + the padded static config.

    Everything shape-defining is padded to the grid maxima and stacked
    [T, ...] so the whole grid rides one executable as outer-scan xs;
    validity masks (`router_mask`, `blocked`) make padded router rows
    provably inert (a blocked row is never proposed, a masked row never
    contributes to a table mean).
    """
    from repro.core import topology
    from repro.core.noc import uniform_mesh_mean_hops

    cfg = sim.cfg
    g = gs[0]
    cfgs = tuple(cfg.with_topology(n_chiplets=c, gateways_per_chiplet=g,
                                   mesh_radix=r)
                 for c, r in zip(cs, rs))
    t_pts = len(cfgs)
    c_max = max(cs)
    shapes = [topology.lut_shape(c) for c in cfgs]
    x_max = max(s[0] for s in shapes)
    y_max = max(s[1] for s in shapes)
    r_max = max(c.routers_per_chiplet for c in cfgs)
    d_pad = max(topology.max_hops(c) for c in cfgs) + 1
    a_bound = max(topology.centrality_bound(c) for c in cfgs)
    big_bound = 4 * (x_max + y_max)

    hop = np.full((t_pts, r_max, x_max, y_max), d_pad, np.int32)
    edge = np.zeros((t_pts, x_max, y_max), np.int32)
    rmask = np.zeros((t_pts, r_max), np.float32)
    caps = np.zeros((t_pts, g), np.int32)
    coords = np.zeros((t_pts, r_max, 2), np.int32)
    blocked = np.ones((t_pts, r_max), np.float32)
    dpos = np.zeros((t_pts, g, 2), np.int32)
    for t, c in enumerate(cfgs):
        r_t = c.routers_per_chiplet
        bx, by = topology.lut_shape(c)
        hop[t, :r_t, :bx, :by] = topology.hop_lut(c)
        edge[t, :bx, :by] = topology.edge_lut(c)
        rmask[t, :r_t] = 1.0
        caps[t] = [-(-r_t // lvl) for lvl in range(1, g + 1)]
        coords[t, :r_t] = topology.router_coords(c)
        blocked[t, :r_t] = 0.0
        dpos[t] = normalize_placement(resolve_gateway_positions(c), c)

    topo = {
        "n_chiplets": jnp.asarray(cs, jnp.int32),
        "g_max": jnp.asarray(gs, jnp.int32),
        "mesh_hops": jnp.asarray(
            [uniform_mesh_mean_hops(c) for c in cfgs], jnp.float32),
        "feed": jnp.asarray(
            [topology.feed_width(c) for c in cfgs], jnp.float32),
        "total_gateways": jnp.asarray(
            [c.total_gateways for c in cfgs], jnp.float32),
        "mx": jnp.asarray([c.mesh_x for c in cfgs], jnp.int32),
        "my": jnp.asarray([c.mesh_y for c in cfgs], jnp.int32),
        "hop_lut": jnp.asarray(hop),
        "edge_lut": jnp.asarray(edge),
        "router_mask": jnp.asarray(rmask),
        "caps": jnp.asarray(caps),
        "coords": jnp.asarray(coords),
        "blocked": jnp.asarray(blocked),
        "default_pos": jnp.asarray(dpos),
    }
    sim_padded = dataclasses.replace(sim, cfg=dataclasses.replace(
        cfg, n_chiplets=c_max, max_gateways_per_chiplet=g, mesh_x=x_max,
        mesh_y=y_max, gateway_positions=None))
    db_per_hop = float(cfg.router_pitch_mm
                       * PHOTONIC_POWER.waveguide_db_per_mm)
    statics = dict(d_pad=int(d_pad), db_per_hop=db_per_hop,
                   a_bound=int(a_bound), big_bound=int(big_bound))
    return sim_padded, topo, cfgs, c_max, statics


def _codesign_batch(trace, c_max):
    """Accept a trace dict, a stacked batch, or a list of W workloads."""
    from repro.core import simulator as _sim

    if isinstance(trace, dict) and jnp.ndim(trace["ext_load"]) == 3:
        batch = trace
    else:
        batch = stack_traces(
            list(trace) if isinstance(trace, (list, tuple)) else [trace],
            pad=True)
    return _sim._topo_trace_arrays(batch, c_max), batch


def _knob_overrides(knobs: dict, islands: int, sim) -> Dict[str, jax.Array]:
    """[K] override arrays; gateway bounds always present (clamped per
    topology point inside the scan, mirroring sweep_topology)."""
    ov = {f: jnp.asarray(v) for f, v in knobs.items()}
    user_max = ov.pop("max_gateways", jnp.int32(sim.ctl.max_gateways))
    user_min = ov.pop("min_gateways", jnp.int32(sim.ctl.min_gateways))
    ov["max_gateways"] = jnp.broadcast_to(
        jnp.asarray(user_max, jnp.int32), (islands,))
    ov["min_gateways"] = jnp.broadcast_to(
        jnp.asarray(user_min, jnp.int32), (islands,))
    return ov


def _codesign_operands(trace, sim, *, islands: int = None,
                       generations: int = 10, population: int = 8,
                       migrate_every: int = 4, archive: int = 32,
                       knob_grids: Optional[dict] = None, seed: int = 0,
                       temperature: float = 0.05, cooling: float = 0.7,
                       restart_frac: float = 0.25, **topo_grids):
    """(operands, statics, info): exactly what the device engine feeds
    `_codesign_jit`. Shared by `search_codesign` and the runtime cache's
    "search" AOT builder, so a pre-compiled executable is guaranteed to
    see operands identical to the jit path's."""
    _check_codesign_params(generations, population, migrate_every, archive)
    cs, gs, rs = _check_topology_grids(sim, topo_grids)
    knobs, islands = _check_knob_grids(knob_grids, islands)
    sim_p, topo, _cfgs, c_max, statics = _prepare_codesign(sim, cs, gs, rs)
    (ext, mem, intra, ext_frac, t_mask, dest), _batch = \
        _codesign_batch(trace, c_max)
    ov = _knob_overrides(knobs, islands, sim)
    weights = jnp.asarray(island_weights(islands))
    hyper = {"temperature": jnp.float32(temperature),
             "cooling": jnp.float32(cooling),
             "restart_frac": jnp.float32(restart_frac)}
    key = jax.random.PRNGKey(seed)
    static = dict(sim=sim_p, generations=generations, population=population,
                  migrate_every=migrate_every, archive=archive,
                  moves_hi=max(1, generations // 3), **statics)
    info = {"cs": cs, "gs": gs, "rs": rs, "knobs": knobs,
            "islands": islands, "workloads": int(ext.shape[0])}
    return ((key, topo, ov, weights, hyper, ext, mem, intra, ext_frac,
             t_mask, dest), static, info)


def _as_placement(pos) -> tuple:
    return tuple((int(x), int(y)) for x, y in np.asarray(pos))


def _codesign_result(arch: dict, hist, inc_pos, inc_s, weights, cs, gs, rs,
                     knobs, islands, engine, meta) -> dict:
    """Shared device/host result assembly (host-side numpy)."""
    obj = np.asarray(arch["obj"], np.float64)
    pos = np.asarray(arch["pos"])
    tix = np.asarray(arch["topo"])
    kix = np.asarray(arch["island"])
    valid = np.asarray(arch["valid"])
    front = []
    for i in range(obj.shape[0]):
        if not valid[i]:
            continue
        t, k = int(tix[i]), int(kix[i])
        entry = {
            "objectives": dict(zip(("latency", "power_mw", "energy"),
                                   (float(v) for v in obj[i]))),
            "placement": _as_placement(pos[i]),
            "topology": {"n_chiplets": cs[t],
                         "gateways_per_chiplet": gs[t],
                         "mesh_radix": rs[t]},
            "knobs": {f: v[k] for f, v in knobs.items()},
            "topology_index": t,
            "island": k,
        }
        front.append(entry)
    front.sort(key=lambda e: (e["objectives"]["latency"],
                              e["objectives"]["power_mw"],
                              e["objectives"]["energy"]))
    hist = np.asarray(hist, np.float64)
    out = {
        "front": front,
        "objectives": PARETO_OBJECTIVES,
        "archive": {"objectives": obj, "valid": valid,
                    "topology_index": tix, "island": kix,
                    "placements": [_as_placement(p) for p in pos]},
        "history": {k: hist[..., i]
                    for i, k in enumerate(CODESIGN_HISTORY_KEYS)},
        "island_incumbents": [[_as_placement(p) for p in per_t]
                              for per_t in np.asarray(inc_pos)],
        "island_scores": np.asarray(inc_s, np.float64),
        "weights": np.asarray(weights, np.float64),
        "grid": {"n_chiplets": list(cs),
                 "gateways_per_chiplet": list(gs),
                 "mesh_radix": list(rs)},
        "knob_grids": {f: list(v) for f, v in knobs.items()},
        "islands": islands,
        "engine": engine,
    }
    out.update(meta)
    return out


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def search_codesign(trace, sim, *, islands: int = None,
                    generations: int = 10, population: int = 8,
                    migrate_every: int = 4, archive: int = 32,
                    knob_grids: Optional[dict] = None, seed: int = 0,
                    temperature: float = 0.05, cooling: float = 0.7,
                    restart_frac: float = 0.25, engine: str = "device",
                    devices=None, **topo_grids) -> dict:
    """Joint topology x placement x knob Pareto search, ONE dispatch.

    ::

        search_codesign(traces, sim,
                        n_chiplets=[64, 144, 256],
                        mesh_radix=[4, 4, 4],
                        knob_grids={"l_m": [0.008, 0.012, 0.02, 0.03]},
                        islands=4)

    Topology axes (`n_chiplets` / `gateways_per_chiplet` / `mesh_radix`)
    are zipped length-T grids scanned by an outer `lax.scan`; within each
    point, K annealed island chains (PR-5 semantics: collision-free
    moves + restarts, spread ordering, annealed metropolis acceptance)
    search placements under K scalarization weight vectors, zipped with
    optional length-K `knob_grids` runtime overrides. Every scored
    candidate feeds a device-resident Pareto archive over
    (mean_latency, mean_power_mw, mean_energy); islands exchange
    incumbents on a ring every `migrate_every` generations. `trace` is a
    single trace dict or a list of W workload traces (objectives average
    over workloads). The whole search compiles to ONE executable launch
    (`engine_stats()["search_dispatches"]` += 1) and the final result
    pytree is the only device->host transfer.

    `engine="host"` runs the identical searcher semantics as a
    host-driven loop over `sweep_topology_batch` (the parity oracle —
    different PRNG streams, same scoring path, same archive rules).
    Pass `devices` (more than one) to shard the island axis via
    `GridSharding`; the island count must then divide over them. With
    `devices=None` the search shards over `jax.devices()` when the island
    count divides over them and runs on one device otherwise (the result
    carries a `"sharding"` entry only when it was sharded).

    Returns the Pareto front as `"front"` entries — each a (topology,
    placement, knobs, objectives) record — plus the raw archive,
    per-(topology, generation) history, island incumbents/weights and
    the searched grids.
    """
    from repro.core import simulator as _sim

    if engine not in ("device", "host"):
        raise ValueError(f"unknown engine {engine!r} (device|host)")
    _check_codesign_params(generations, population, migrate_every, archive)
    cs, gs, rs = _check_topology_grids(sim, topo_grids)
    knobs, islands = _check_knob_grids(knob_grids, islands)
    if devices is not None and len(devices) > 1 and islands % len(devices):
        raise ValueError(
            f"islands={islands} does not divide over {len(devices)} devices "
            f"(the migration ring admits no padded islands)")

    if engine == "host":
        return _host_codesign(
            trace, sim, cs, gs, rs, knobs, islands,
            generations=generations, population=population,
            migrate_every=migrate_every, archive=archive, seed=seed,
            temperature=temperature, cooling=cooling,
            restart_frac=restart_frac)

    built, static, info = _codesign_operands(
        trace, sim, islands=islands, generations=generations,
        population=population, migrate_every=migrate_every, archive=archive,
        knob_grids=knob_grids, seed=seed, temperature=temperature,
        cooling=cooling, restart_frac=restart_frac, **topo_grids)
    (key, topo, ov, weights, hyper, ext, mem, intra, ext_frac, t_mask,
     dest) = built
    w_axis = info["workloads"]

    devices = list(devices if devices is not None else jax.devices())
    sharding = None
    if len(devices) > 1 and islands % len(devices) == 0:
        from repro.core.distributed import GridSharding

        gsh = GridSharding(islands, devices=devices, logical_axis="islands")
        ov_s, w_s = gsh.shard((ov, weights))
        topo_r, hyper_r, ext_r, mem_r, intra_r, frac_r, mask_r, \
            dest_r = gsh.replicate((topo, hyper, ext, mem, intra,
                                    ext_frac, t_mask, dest))
        res = _codesign_jit(key, topo_r, ov_s, w_s, hyper_r, ext_r,
                            mem_r, intra_r, frac_r, mask_r, dest_r, **static)
        sharding = gsh.describe()
    else:
        res = _codesign_jit(key, topo, ov, weights, hyper, ext, mem, intra,
                            ext_frac, t_mask, dest, **static)
    # Counted after the launch (PR-5 convention): a raising compile never
    # inflates the one-search == one-dispatch stats.
    _sim._STATS["search_dispatches"] += 1
    host = jax.device_get(res)          # the ONE transfer for the search

    meta = {"generations": generations, "population": population,
            "migrate_every": migrate_every, "archive_capacity": archive,
            "workloads": w_axis,
            "candidate_evals": len(cs) * generations * islands
            * population * w_axis}
    if sharding is not None:
        meta["sharding"] = sharding
    return _codesign_result(host["archive"], host["history"],
                            host["island_incumbents"],
                            host["island_scores"], np.asarray(weights),
                            cs, gs, rs, knobs, islands, "device", meta)


# ---------------------------------------------------------------------------
# Host engine (parity oracle) + front re-scoring
# ---------------------------------------------------------------------------

def _host_propose(parent, cfg_t, coords, rng, moves, restart_frac, g):
    """One host candidate: restart or 1-2 collision-free moves, spread-
    ordered — the device proposal semantics with numpy randomness."""
    if rng.rand() < restart_frac:
        idx = rng.choice(len(coords), size=g, replace=False)
        pos = [coords[int(i)] for i in idx]
    else:
        pos = list(parent)
        for _ in range(moves):
            i = int(rng.randint(g))
            occupied = set(pos)
            free = [c for c in coords if c not in occupied]
            if not free:
                break
            pos[i] = free[int(rng.randint(len(free)))]
    return normalize_placement(pos, cfg_t, order="spread")


def _host_codesign(trace, sim, cs, gs, rs, knobs, islands, *, generations,
                   population, migrate_every, archive, seed, temperature,
                   cooling, restart_frac) -> dict:
    """Host-driven mirror of the device search (the parity oracle).

    Identical searcher semantics — same migration/acceptance/archive
    rules, same per-point knob clamps (delegated to `sweep_topology_batch`
    whose `_prepare_topology_sweep` applies them) — with numpy randomness
    and one public sweep call per (topology point, generation). Same
    return structure as the device engine; the PRNG streams differ, so
    the two engines walk different, equally valid trajectories.
    """
    from repro.core import simulator as _sim
    from repro.core import topology

    g = gs[0]
    cfg = sim.cfg
    cfgs = [cfg.with_topology(n_chiplets=c, gateways_per_chiplet=g,
                              mesh_radix=r) for c, r in zip(cs, rs)]
    if isinstance(trace, dict) and jnp.ndim(trace["ext_load"]) == 3:
        batch = trace
    else:
        batch = stack_traces(
            list(trace) if isinstance(trace, (list, tuple)) else [trace],
            pad=True)
    w_axis = int(jnp.shape(batch["ext_load"])[0])
    weights = island_weights(islands).astype(np.float64)
    rng = np.random.RandomState(seed)
    moves_hi = max(1, generations // 3)
    lanes = islands * population
    arch = _empty_archive_np(archive, g)
    hist = np.zeros((len(cfgs), generations, len(CODESIGN_HISTORY_KEYS)))
    inc_pos_all, inc_s_all = [], []

    for t, cfg_t in enumerate(cfgs):
        coords = [tuple(int(v) for v in c)
                  for c in topology.router_coords(cfg_t)]
        dflt = normalize_placement(resolve_gateway_positions(cfg_t), cfg_t)
        parent = [dflt] * islands
        inc_pos = list(parent)
        inc_s = np.full((islands,), np.inf)
        norm = np.ones((islands, 3))
        for gen in range(generations):
            if migrate_every > 0 and gen > 0 \
                    and gen % migrate_every == 0:
                parent = [inc_pos[(k - 1) % islands]
                          for k in range(islands)]
            moves = 2 if gen < moves_hi else 1
            cands = [[parent[k]]
                     + [_host_propose(parent[k], cfg_t, coords, rng,
                                      moves, restart_frac, g)
                        for _ in range(population - 1)]
                     for k in range(islands)]

            grids = {"n_chiplets": [cs[t]] * lanes,
                     "gateways_per_chiplet": [g] * lanes,
                     "mesh_radix": [rs[t]] * lanes,
                     "gateway_positions": [cands[k][p]
                                           for k in range(islands)
                                           for p in range(population)]}
            for f, vals in knobs.items():
                grids[f] = [vals[k] for k in range(islands)
                            for _ in range(population)]
            out = _sim.sweep_topology_batch(batch, sim, **grids)
            objs = np.stack(
                [np.asarray(out["summary"][m], np.float64).mean(axis=0)
                 for m in PARETO_OBJECTIVES],
                axis=-1).reshape(islands, population, 3)

            if gen == 0:
                norm = objs[:, 0, :].copy()
            denom = np.maximum(np.abs(norm), 1e-12)
            s = np.sum(weights[:, None, :] * objs / denom[:, None, :],
                       axis=-1)
            ib = np.argmin(s, axis=1)
            sb = s[np.arange(islands), ib]
            cb = [cands[k][int(ib[k])] for k in range(islands)]
            for k in range(islands):
                if sb[k] < inc_s[k]:
                    inc_s[k] = sb[k]
                    inc_pos[k] = cb[k]
            u = rng.rand(islands)
            temp = temperature * cooling ** gen
            for k in range(islands):
                delta = sb[k] - s[k, 0]
                rel = delta / max(abs(s[k, 0]), 1e-12)
                metropolis = temp > 0 \
                    and u[k] < np.exp(-rel / max(temp, 1e-30))
                if delta < 0 or metropolis:
                    parent[k] = cb[k]
            arch = _archive_insert_np(
                arch, objs.reshape(-1, 3),
                np.asarray([cands[k][p] for k in range(islands)
                            for p in range(population)], np.int32),
                np.full((lanes,), t, np.int32),
                np.repeat(np.arange(islands, dtype=np.int32), population),
                archive)
            hist[t, gen] = [float(np.sum(arch["valid"])),
                            float(np.min(inc_s))]
        inc_pos_all.append([np.asarray(p, np.int32) for p in inc_pos])
        inc_s_all.append(inc_s.copy())

    meta = {"generations": generations, "population": population,
            "migrate_every": migrate_every, "archive_capacity": archive,
            "workloads": w_axis,
            "candidate_evals": len(cfgs) * generations * islands
            * population * w_axis}
    return _codesign_result(arch, hist, np.asarray(inc_pos_all),
                            np.asarray(inc_s_all), weights, cs, gs, rs,
                            knobs, islands, "host", meta)


def rescore_front_host(result, trace, sim) -> np.ndarray:
    """Re-score a co-design front through the public host sweep path.

    Every front entry becomes one `sweep_topology_batch` lane — its
    topology point, its (already spread-ordered) placement pinned via the
    `gateway_positions` axis, its island knobs as runtime-override lanes
    — and the per-workload objective summaries average exactly like the
    in-scan scoring. The returned [n_front, 3] array matches the device
    archive's objectives to float tolerance (the 1e-6 parity oracle in
    tests/test_pareto.py): same masked scan body, reached through a
    completely different (host-prepared, unfused) path.
    """
    from repro.core import simulator as _sim

    entries = result["front"]
    if not entries:
        return np.zeros((0, 3), np.float64)
    if isinstance(trace, dict) and jnp.ndim(trace["ext_load"]) == 3:
        batch = trace
    else:
        batch = stack_traces(
            list(trace) if isinstance(trace, (list, tuple)) else [trace],
            pad=True)
    grids = {
        "n_chiplets": [e["topology"]["n_chiplets"] for e in entries],
        "gateways_per_chiplet": [e["topology"]["gateways_per_chiplet"]
                                 for e in entries],
        "mesh_radix": [e["topology"]["mesh_radix"] for e in entries],
        "gateway_positions": [e["placement"] for e in entries],
    }
    for f in result.get("knob_grids", {}):
        grids[f] = [e["knobs"][f] for e in entries]
    out = _sim.sweep_topology_batch(batch, sim, **grids)
    return np.stack(
        [np.asarray(out["summary"][m], np.float64).mean(axis=0)
         for m in PARETO_OBJECTIVES], axis=-1)
