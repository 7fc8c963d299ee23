"""Device-resident gateway-placement search (PR 5).

ReSiPI's headline claim is *run-time* reconfiguration — redeploying
inter-chiplet gateways against observed traffic — which makes placement
search a serving-path workload, not an offline design step. The PR-3
`search_placement` host loop (numpy proposals, one dispatch plus several
device->host syncs per generation) tops out around a hundred generations
per second on CPU: the compiled sweep engine underneath it idles while
Python shuttles candidates back and forth.

This module moves the ENTIRE annealed search on-device. Proposal
generation (collision-free single-gateway moves + random restarts via
`jax.random`, spread-ordered by the traceable
`gateway_controller.activation_order_jnp`), candidate table construction
(`selection.placement_tables_jnp` — the jnp twin of the numpy builder),
candidate scoring (the existing masked scan body), annealed acceptance,
elitist best-tracking and the per-generation history all live inside ONE
`lax.scan` with a donated carry:

  * `search_placement_device` — a full search is a single dispatch with
    zero host round-trips between generations (`engine_stats()` shows one
    scan-body trace and one `search_dispatches` per search). The public
    entry point is `simulator.search_placement` (engine="device" default,
    engine="host" keeps the PR-3 loop as the parity oracle).
  * `search_placement_islands` — K independent annealed chains vmapped
    over seeds, sharing the single executable (embarrassingly parallel
    restarts). Runtime `SWEEPABLE_FIELDS` grids of length K zip with the
    island axis, so "search the placement under l_m[k]" is a joint
    placement x runtime-knob exploration in one compiled call; the island
    axis shards across devices via NamedSharding when more than one is
    present.

Proposal/acceptance semantics mirror the host loop exactly (same move
kinds, same annealing law, same elitism and default-scheme scoring in
generation 0); the PRNG streams differ (`jax.random` vs numpy
RandomState), so the two engines explore different — equally valid —
trajectories from the same seed while each stays fully deterministic.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constants import NetworkConfig
from repro.core.gateway_controller import activation_order_jnp
from repro.core.selection import (_router_coords, normalize_placement,
                                  placement_tables_jnp,
                                  resolve_gateway_positions)
# One source of truth with the host engine: the summary schema (fixed
# vector order for the elitist best-candidate carry), the short objective
# aliases and the objective validator all live next to _summary_from_sums.
# (simulator does not import this module at top level, so this import is
# cycle-free.)
from repro.core.simulator import (PLACEMENT_OBJECTIVE_ALIASES, SUMMARY_KEYS,
                                  check_placement_objective)


def _objective_value(out: dict, objective: str) -> jax.Array:
    """Scalar objective from one candidate's simulate output (traced)."""
    if objective == "inter_latency":
        return jnp.mean(out["records"]["mean_inter_latency"])
    return out["summary"][
        PLACEMENT_OBJECTIVE_ALIASES.get(objective, objective)]


def _mesh_coords(cfg: NetworkConfig) -> jnp.ndarray:
    """[R, 2] router coordinates, flat index x*mesh_y + y.

    Same ordering as `selection._router_coords` (which
    `placement_tables_jnp` builds against) — `_one_move`'s flat-index
    occupancy test depends on the two staying in lockstep.
    """
    return jnp.asarray(_router_coords(cfg), jnp.int32)


# ---------------------------------------------------------------------------
# On-device proposal kernels
# ---------------------------------------------------------------------------
#
# All random draws are pre-generated OUTSIDE the generation scan in a few
# vectorized jax.random calls (threefry per tiny in-scan draw is the single
# biggest CPU cost of a naive port): the scan body consumes pre-drawn
# restart flags / restart placements / move indices / Gumbel noise and does
# only arithmetic. Data-dependent choices (which *free* router a gateway
# moves to) use the pre-drawn Gumbel noise via masked argmax — exactly a
# categorical sample over the free slots.

def _one_move(pos: jax.Array, i: jax.Array, gumbel: jax.Array,
              coords: jax.Array,
              blocked: jax.Array) -> jax.Array:
    """Collision-free single-gateway move (host `mutate` semantics).

    Relocates gateway `i` to a router chosen uniformly among the currently
    unoccupied ones (the mover's own slot counts as occupied, exactly like
    the host loop, so a move never stays in place). `blocked` [R] marks
    routers excluded from the proposal space (failed hardware) — they count
    as permanently occupied. Scatter-free on purpose — tiny batched
    scatters lower poorly on CPU, and this runs per candidate per
    generation inside the search scan. Occupancy is a coordinate-equality
    test against `coords` rows, so arbitrary layouts (explicit
    NetworkConfig.coords) need no flat-index arithmetic.
    """
    g_max = pos.shape[0]
    occupied = jnp.any(
        jnp.all(coords[:, None, :] == pos[None, :, :], axis=-1), axis=1)
    occupied = occupied | (blocked > 0.5)
    j = jnp.argmax(jnp.where(occupied, -jnp.inf, gumbel))
    # No free router (placement fills the mesh): skip the move, exactly
    # like the host loop's empty-free-list break.
    movable = jnp.any(~occupied)
    return jnp.where(movable & (jnp.arange(g_max)[:, None] == i),
                     coords[j], pos)


def _propose(parent: jax.Array, restart: jax.Array,
             restart_pos: jax.Array, move_i: jax.Array,
             move_gumbel: jax.Array, moves: jax.Array, coords: jax.Array,
             blocked: jax.Array, cfg: NetworkConfig) -> jax.Array:
    """One candidate: random restart or 1-2 collision-free moves, then
    spread-reordered by the traceable activation rule (host parity)."""
    m1 = _one_move(parent, move_i[0], move_gumbel[0], coords, blocked)
    m2 = _one_move(m1, move_i[1], move_gumbel[1], coords, blocked)
    pos = jnp.where(restart, restart_pos, jnp.where(moves > 1, m2, m1))
    return pos[activation_order_jnp(pos, cfg)]


# ---------------------------------------------------------------------------
# The one-scan search core
# ---------------------------------------------------------------------------

# One history record per generation, packed as a single [len(HISTORY_KEYS)]
# vector so the scan emits one ys leaf (fewer per-step update ops).
HISTORY_KEYS = ("generation", "parent_score", "best_candidate_score",
                "best_score", "accepted", "latency", "power_mw", "energy")


def _search_core(carry0: dict, key: jax.Array, ext, mem, intra, ext_frac,
                 t_mask, default_pos: jax.Array, hyper: dict,
                 ov: Dict[str, jax.Array], blocked: jax.Array, dest=None,
                 *, sim, generations: int, population: int, objective: str,
                 inject_default: bool, moves_hi: int) -> dict:
    """The whole annealed search as ONE `lax.scan` over generations.

    Every generation: propose population-1 candidates on device, build
    their placement tables with the jnp twins, score all of them through
    the existing masked scan body (one vmap), apply annealed acceptance to
    the incumbent and elitist best-tracking — no value ever crosses to the
    host. All randomness is pre-drawn from `key` in a handful of vectorized
    calls before the scan; the scan carry is donated by the jit wrappers,
    so a warm search reuses its buffers in place.
    """
    from repro.core import simulator as _sim

    cfg = sim.cfg
    coords = _mesh_coords(cfg)
    n_r = coords.shape[0]
    g_max = cfg.max_gateways_per_chiplet
    n_prop = population - 1

    k_flag, k_perm, k_idx, k_gum, k_acc = jax.random.split(key, 5)
    restart = jax.random.bernoulli(k_flag, hyper["restart_frac"],
                                   (generations, n_prop))
    # Restart placements: Gumbel-top-k = a uniform sample of g_max routers
    # WITHOUT replacement over the allowed (non-blocked) ones. With nothing
    # blocked this is distributionally the random permutation the engine
    # used pre-faults; blocking `blocked` routers just renormalizes it.
    rest_gum = jnp.where(blocked[None, None, :] > 0.5, -jnp.inf,
                         jax.random.gumbel(k_perm,
                                           (generations, n_prop, n_r)))
    _, rest_idx = jax.lax.top_k(rest_gum, g_max)
    restart_pos = coords[rest_idx]             # [T, n_prop, G, 2]
    move_i = jax.random.randint(k_idx, (generations, n_prop, 2), 0, g_max)
    move_gum = jax.random.gumbel(k_gum, (generations, n_prop, 2, n_r))
    acc_u = jax.random.uniform(k_acc, (generations,))

    def gen_body(carry, xs):
        gen, rst, rst_pos, mv_i, mv_gum, u = xs
        # Host schedule: 2 moves for the first max(1, generations//3)
        # generations (coarse), 1 afterwards (fine).
        moves = jnp.where(gen < moves_hi, 2, 1)
        props = jax.vmap(
            lambda r, rp, mi, mg: _propose(carry["parent"], r, rp, mi, mg,
                                           moves, coords, blocked, cfg)
        )(rst, rst_pos, mv_i, mv_gum)
        cands = jnp.concatenate([carry["parent"][None], props])  # [P, G, 2]
        if inject_default:
            # Host: generation 0 always scores the default edge scheme when
            # the search starts elsewhere (init != default).
            cands = cands.at[1].set(
                jnp.where(gen == 0, default_pos, cands[1]))

        tables = jax.vmap(lambda p: placement_tables_jnp(p, cfg))(cands)

        def score_one(tbl):
            out = _sim._simulate_impl(ext, mem, intra, ext_frac, t_mask,
                                      sim, tbl, ov, dest=dest)
            return (_objective_value(out, objective),
                    jnp.stack([out["summary"][k] for k in SUMMARY_KEYS]))

        scores, summaries = jax.vmap(score_one)(tables)   # [P], [P, 8]

        default_lane = 1 if inject_default else 0
        default_score = jnp.where(gen == 0, scores[default_lane],
                                  carry["default_score"])

        # Elitist best over everything ever scored.
        ibest = jnp.argmin(scores)
        sbest = scores[ibest]
        improved = sbest < carry["best_score"]
        best_score = jnp.where(improved, sbest, carry["best_score"])
        best_pos = jnp.where(improved, cands[ibest], carry["best_pos"])
        best_summary = jnp.where(improved, summaries[ibest],
                                 carry["best_summary"])

        # Annealed incumbent move: greedy downhill, probabilistic uphill.
        delta = sbest - scores[0]
        rel = delta / jnp.maximum(jnp.abs(scores[0]), 1e-12)
        temp = (hyper["temperature"]
                * hyper["cooling"] ** gen.astype(jnp.float32))
        metropolis = (temp > 0) & (u < jnp.exp(-rel / jnp.maximum(temp,
                                                                  1e-30)))
        accepted = (delta < 0) | metropolis
        parent = jnp.where(accepted, cands[ibest], carry["parent"])

        lat_i = SUMMARY_KEYS.index("mean_latency")
        pow_i = SUMMARY_KEYS.index("mean_power_mw")
        en_i = SUMMARY_KEYS.index("mean_energy")
        rec = jnp.stack([gen.astype(jnp.float32), scores[0], sbest,
                         best_score, accepted.astype(jnp.float32),
                         summaries[ibest, lat_i], summaries[ibest, pow_i],
                         summaries[ibest, en_i]])
        new_carry = {"parent": parent, "best_pos": best_pos,
                     "best_score": best_score, "best_summary": best_summary,
                     "default_score": default_score}
        return new_carry, rec

    carry, history = jax.lax.scan(
        gen_body, carry0,
        (jnp.arange(generations, dtype=jnp.int32), restart, restart_pos,
         move_i, move_gum, acc_u))
    # Returning the final incumbent (a) lets callers warm-restart a search
    # from where annealing left off and (b) gives every donated carry
    # buffer a same-shape output slot, so donation is fully usable.
    return {"best_placement": carry["best_pos"],
            "best_score": carry["best_score"],
            "best_summary": carry["best_summary"],
            "default_score": carry["default_score"],
            "incumbent_placement": carry["parent"],
            "history": history}


def _init_carry(init_pos: jax.Array) -> dict:
    # parent/best_pos must be distinct buffers: the carry is donated, and
    # XLA rejects the same buffer appearing in two donated slots.
    return {"parent": jnp.array(init_pos, jnp.int32, copy=True),
            "best_pos": jnp.array(init_pos, jnp.int32, copy=True),
            "best_score": jnp.float32(jnp.inf),
            "best_summary": jnp.zeros((len(SUMMARY_KEYS),), jnp.float32),
            "default_score": jnp.float32(0.0)}


_SEARCH_STATICS = ("sim", "generations", "population", "objective",
                   "inject_default", "moves_hi")


@functools.partial(jax.jit, static_argnames=_SEARCH_STATICS,
                   donate_argnums=(0,))
def _search_jit(carry0, key, ext, mem, intra, ext_frac, t_mask,
                default_pos, hyper, ov, blocked, dest=None, *, sim,
                generations, population, objective, inject_default,
                moves_hi):
    return _search_core(carry0, key, ext, mem, intra, ext_frac, t_mask,
                        default_pos, hyper, ov, blocked, dest, sim=sim,
                        generations=generations, population=population,
                        objective=objective, inject_default=inject_default,
                        moves_hi=moves_hi)


@functools.partial(jax.jit, static_argnames=_SEARCH_STATICS,
                   donate_argnums=(0,))
def _search_islands_jit(carry0, key, ext, mem, intra, ext_frac, t_mask,
                        default_pos, hyper, ov, blocked, dest=None, *, sim,
                        generations, population, objective, inject_default,
                        moves_hi):
    """K chains, ONE executable: vmap over (carry, key, overrides)."""
    return jax.vmap(
        lambda c0, ks, o: _search_core(
            c0, ks, ext, mem, intra, ext_frac, t_mask, default_pos, hyper,
            o, blocked, dest, sim=sim, generations=generations,
            population=population, objective=objective,
            inject_default=inject_default, moves_hi=moves_hi)
    )(carry0, key, ov)


def clear_search_caches() -> None:
    """Drop the compiled search executables (cold-start measurement)."""
    _search_jit.clear_cache()
    _search_islands_jit.clear_cache()


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def _check_search_params(generations: int, population: int,
                         objective: str) -> None:
    if population < 2:
        raise ValueError("population must be >= 2 (incumbent + candidates)")
    if generations < 1:
        raise ValueError("generations must be >= 1")
    check_placement_objective(objective)


def repair_placement(placement, blocked_positions, cfg) -> tuple:
    """Move gateways off blocked routers to the nearest allowed free ones.

    Host-side (numpy) helper for warm-restarting a search from an
    incumbent that predates a failure: every gateway sitting on a blocked
    router relocates to the Manhattan-nearest unoccupied allowed router
    (deterministic: ties break by flat router index). Returns a
    spread-normalized placement that is valid under `blocked_positions`.
    """
    from repro.core import topology

    p = list(normalize_placement(placement, cfg))
    blocked = {(int(x), int(y)) for (x, y) in blocked_positions}
    occupied = set(p)
    free = [(int(x), int(y)) for x, y in topology.router_coords(cfg)
            if (x, y) not in blocked and (x, y) not in occupied]
    for i, pos in enumerate(p):
        if pos not in blocked:
            continue
        if not free:
            raise ValueError(
                f"cannot repair placement: {len(blocked)} blocked routers "
                f"leave no free position for the gateway at {pos}")
        j = min(range(len(free)),
                key=lambda k: (int(topology.pair_hops(cfg, free[k], pos)),
                               k))
        p[i] = free.pop(j)
    return normalize_placement(p, cfg, order="spread")


def _blocked_mask(blocked_positions, cfg) -> jnp.ndarray:
    """[R] float mask in `_mesh_coords` row order (1 = excluded router)."""
    from repro.core import topology

    idx_lut = topology.router_index_lut(cfg)
    bx, by = idx_lut.shape
    mask = np.zeros(cfg.routers_per_chiplet, np.float32)
    for (x, y) in (blocked_positions or ()):
        x, y = int(x), int(y)
        r = int(idx_lut[x, y]) if (0 <= x < bx and 0 <= y < by) else -1
        if r < 0:
            raise ValueError(f"blocked position ({x}, {y}) is outside the "
                             f"{bx}x{by} mesh")
        mask[r] = 1.0
    return jnp.asarray(mask)


def _prepare_search(trace: dict, sim, init, blocked_positions=None):
    """Shared setup: trace arrays, default/init placements, static flags.

    Blocked routers shrink the proposal space as a *traced* [R] mask, so
    every blocked set (including the empty one) shares the same compiled
    search executable. The scored default placement is repaired off blocked
    hardware; an `init` occupying a blocked router raises (callers repair
    explicitly so the warm-restart move cost is attributable).
    """
    from repro.core import simulator as _sim

    arrays = _sim._trace_arrays(trace)
    cfg = sim.cfg
    blocked = {(int(x), int(y)) for (x, y) in (blocked_positions or ())}
    g_max = cfg.max_gateways_per_chiplet
    if cfg.routers_per_chiplet - len(blocked) < g_max:
        raise ValueError(
            f"{len(blocked)} blocked routers leave fewer than "
            f"{g_max} allowed positions on the "
            f"{cfg.mesh_x}x{cfg.mesh_y} mesh")
    default_p = normalize_placement(resolve_gateway_positions(cfg), cfg)
    if set(default_p) & blocked:
        default_p = repair_placement(default_p, blocked, cfg)
    parent_p = default_p if init is None else normalize_placement(init, cfg)
    if set(parent_p) & blocked:
        raise ValueError(
            f"init placement occupies blocked routers "
            f"{sorted(set(parent_p) & blocked)} — repair it first "
            f"(search.repair_placement)")
    if len(parent_p) != g_max:
        raise ValueError(
            f"init places {len(parent_p)} gateways but "
            f"max_gateways_per_chiplet={g_max}")
    inject_default = parent_p != default_p
    return (arrays, jnp.asarray(default_p, jnp.int32),
            jnp.asarray(parent_p, jnp.int32), default_p, inject_default,
            _blocked_mask(blocked, cfg))


def _hyper(temperature, cooling, restart_frac) -> dict:
    return {"temperature": jnp.float32(temperature),
            "cooling": jnp.float32(cooling),
            "restart_frac": jnp.float32(restart_frac)}


def _history_list(hist: np.ndarray) -> list:
    """[T, len(HISTORY_KEYS)] record matrix -> host-engine list of dicts."""
    out = []
    for row in np.asarray(hist):
        rec = dict(zip(HISTORY_KEYS, (float(v) for v in row)))
        rec["generation"] = int(rec["generation"])
        rec["accepted"] = rec["accepted"] > 0.5
        out.append(rec)
    return out


def _as_placement(pos) -> tuple:
    return tuple((int(x), int(y)) for x, y in np.asarray(pos))


def search_placement_device(trace: dict, sim, *,
                            objective: str = "inter_latency",
                            generations: int = 10, population: int = 12,
                            seed: int = 0, init=None,
                            temperature: float = 0.05, cooling: float = 0.7,
                            restart_frac: float = 0.25,
                            blocked_positions=None) -> dict:
    """Device-resident annealed placement search: ONE dispatch per search.

    Same searcher semantics and return structure as the host engine (see
    `simulator.search_placement`, which wraps this), but the whole
    generation loop is a single compiled `lax.scan`: `engine_stats()` shows
    one scan-body trace for the entire search, `search_dispatches` counts
    exactly one executable launch, and the only device->host transfer is
    the final result pytree.
    """
    from repro.core import simulator as _sim

    _check_search_params(generations, population, objective)
    (ext, mem, intra, ext_frac, t_mask, dest), default_pos, init_pos, \
        default_p, inject_default, blocked = _prepare_search(
            trace, sim, init, blocked_positions)

    res = _search_jit(
        _init_carry(init_pos), jax.random.PRNGKey(seed), ext, mem, intra,
        ext_frac, t_mask, default_pos,
        _hyper(temperature, cooling, restart_frac), {}, blocked, dest,
        sim=sim, generations=generations, population=population,
        objective=objective, inject_default=inject_default,
        moves_hi=max(1, generations // 3))
    # Counted after the launch (like the islands path): a raising
    # compile/trace never inflates the one-search == one-dispatch stats.
    _sim._STATS["search_dispatches"] += 1
    host = jax.device_get(res)          # the ONE transfer for the search

    best_s = float(host["best_score"])
    default_s = float(host["default_score"])
    return {"best_placement": _as_placement(host["best_placement"]),
            "best_score": best_s,
            "best_summary": dict(zip(SUMMARY_KEYS,
                                     map(float, host["best_summary"]))),
            "default_placement": default_p, "default_score": default_s,
            "improvement_frac": 1.0 - best_s / max(default_s, 1e-12),
            "incumbent_placement": _as_placement(
                host["incumbent_placement"]),
            "objective": objective, "generations": generations,
            "population": population, "engine": "device",
            "history": _history_list(host["history"])}


def search_placement_islands(trace: dict, sim, *, islands: int = None,
                             objective: str = "inter_latency",
                             generations: int = 10, population: int = 12,
                             seed: int = 0, init=None,
                             temperature: float = 0.05,
                             cooling: float = 0.7,
                             restart_frac: float = 0.25,
                             devices=None, blocked_positions=None,
                             **grids) -> dict:
    """K independent annealed chains in ONE compiled executable.

    Each island runs the full `search_placement_device` chain from its own
    PRNG stream (`fold_in(seed, k)`), vmapped so all K populations score in
    the same executable launch — embarrassingly parallel restarts at the
    cost of one. Runtime `SWEEPABLE_FIELDS` grids of length K zip with the
    island axis::

        search_placement_islands(tr, sim, islands=4,
                                 l_m=[0.008, 0.012, 0.02, 0.03])

    searches the best placement *per L_m operating point* — a joint
    placement x runtime-knob exploration (the concrete step toward the
    ROADMAP's joint search item). With more than one device the island
    axis is sharded via NamedSharding; a sharded run that fails raises.

    Returns the overall winner plus per-island bests/defaults/histories
    (`island_*` arrays, leading [K] axis), all from one `device_get`.
    """
    from repro.core import simulator as _sim

    _check_search_params(generations, population, objective)
    (ext, mem, intra, ext_frac, t_mask, dest), default_pos, init_pos, \
        default_p, inject_default, blocked = _prepare_search(
            trace, sim, init, blocked_positions)

    unknown = set(grids) - set(_sim.SWEEPABLE_FIELDS)
    if unknown:
        raise ValueError(
            f"non-sweepable fields: {sorted(unknown)} (islands zip with "
            f"runtime fields: {_sim.SWEEPABLE_FIELDS})")
    if islands is not None and (isinstance(islands, bool)
                                or not isinstance(islands,
                                                  (int, np.integer))):
        raise ValueError(
            f"islands must be an int, got {type(islands).__name__} "
            f"{islands!r}")
    lengths = {f: _sim._grid_len(f, v) for f, v in grids.items()}
    if islands is None:
        if lengths:
            if len(set(lengths.values())) != 1:
                raise ValueError(f"swept fields must share one length, "
                                 f"got {lengths}")
            islands = next(iter(lengths.values()))
        else:
            islands = 8
    bad = {f: n for f, n in lengths.items() if n != islands}
    if bad:
        raise ValueError(
            f"island grids must have length islands={islands}, got {bad} "
            f"— every runtime grid zips element-wise with the island axis")
    if islands < 1:
        raise ValueError("islands must be >= 1")

    ov = {f: jnp.asarray(v) for f, v in grids.items()}
    base = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(islands))
    carry0 = jax.vmap(lambda _: _init_carry(init_pos))(jnp.arange(islands))
    hyper = _hyper(temperature, cooling, restart_frac)
    static = dict(sim=sim, generations=generations, population=population,
                  objective=objective, inject_default=inject_default,
                  moves_hi=max(1, generations // 3))

    devices = list(devices if devices is not None else jax.devices())
    if len(devices) > 1:
        from repro.core.distributed import GridSharding

        # The island axis shards over the fleet's "grid" mesh axis — with
        # init_distributed up, across every host's devices. The shared
        # trace/search inputs replicate fleet-wide; the result pytree is
        # all-gathered so every process sees all islands.
        gs = GridSharding(islands, devices=devices, logical_axis="islands")
        carry_s, keys_s, ov_s = gs.shard((carry0, keys, ov))
        ext_r, mem_r, intra_r, frac_r, mask_r, dpos_r, hyper_r, \
            blocked_r, dest_r = gs.replicate(
                (ext, mem, intra, ext_frac, t_mask, default_pos,
                 hyper, blocked, dest))
        res = _search_islands_jit(
            carry_s, keys_s, ext_r, mem_r, intra_r, frac_r, mask_r,
            dpos_r, hyper_r, ov_s, blocked_r, dest_r, **static)
        res = gs.gather(res)
    else:
        res = _search_islands_jit(carry0, keys, ext, mem, intra, ext_frac,
                                  t_mask, default_pos, hyper, ov, blocked,
                                  dest, **static)
    # Counted after the launch: a raising compile never inflates the
    # one-search == one-dispatch accounting.
    _sim._STATS["search_dispatches"] += 1
    host = jax.device_get(res)          # the ONE transfer for all islands

    scores = np.asarray(host["best_score"])
    k_best = int(np.argmin(scores))
    defaults = np.asarray(host["default_score"])
    best_s = float(scores[k_best])
    default_best = float(defaults[k_best])
    hist = np.asarray(host["history"])       # [K, T, len(HISTORY_KEYS)]
    return {
        "best_placement": _as_placement(host["best_placement"][k_best]),
        "best_score": best_s,
        "best_island": k_best,
        "best_summary": dict(zip(
            SUMMARY_KEYS, map(float, host["best_summary"][k_best]))),
        "default_placement": default_p,
        "default_score": default_best,
        "improvement_frac": 1.0 - best_s / max(default_best, 1e-12),
        "island_best_placements": [
            _as_placement(p) for p in host["best_placement"]],
        "island_incumbents": [
            _as_placement(p) for p in host["incumbent_placement"]],
        "island_best_scores": scores,
        "island_default_scores": defaults,
        "island_overrides": {f: np.asarray(v) for f, v in grids.items()},
        "history": {k: hist[..., i] for i, k in enumerate(HISTORY_KEYS)},
        "objective": objective, "generations": generations,
        "population": population, "islands": islands, "engine": "device",
    }
