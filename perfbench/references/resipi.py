"""Plain reference of the epoch-level interval model, for `correct`.

A straightforward numpy transcription of what a configuration file states
(the paper's Table 1 system, its NoC queueing model, power model and
gateway controller) and of the PARSEC-like trace definition. It imports
nothing of the simulator under test: its only inputs are the
configuration file, the traffic parameters and the PRNG keys the harness
hands the program. Random draws come from `jax.random` with those keys;
everything else is numpy.

Arithmetic runs in float64. `Precision("bf16")` rounds every intermediate
to bfloat16 instead: that is the control, the step below the float32 the
configuration states, which the comparison must reject.

Two kinds of decision in the model are discontinuous: the controller's
gateway activation thresholds (Eqs. 6-7 of the paper) and the
per-interval saturation flag. A float32 program and a float64 reference
can land on opposite sides of a threshold when the load sits within
rounding of it. The reference marks such near-ties (within `NEAR` of the
threshold, relative) and admits either outcome there, and nothing else:
a sweep's program records the gateway count of every interval, and at a
near-tie the reference follows the program's choice when that choice is
one of the two (`guide`); the last interval's choice is not recorded, so
its PCM reconfiguration energy is held to the range the near-ties there
allow (`reconf_lo`, `reconf_hi`). Where no record exists (served
sessions), `simulate_admissible` runs every combination of the first
MAX_FLIPS near-ties instead.

The comparison (`lane_gaps`): for one lane and one summary key the gap
is |program - reference| over max(|reference|, the median |reference| of
that key over the compared lanes); `saturated_frac` is held to the
interval that near-ties admit, in its own unit (a share of intervals),
`total_reconfig_nj` to the range that near-ties of the last interval
admit, and `valid_intervals` to the count. A lane's gap is its worst
key, taken on the admissible reference trajectory that fits it best.
The cell's number, `summary_gap`, is the worst lane's.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

import numpy as np

# Relative distance to a threshold inside which a decision counts as a tie.
# float32 trace generation drifts by up to ~1e-5 relative (the phase
# argument reaches ~85 rad), so 1e-3 leaves a factor of a hundred.
NEAR = 1e-3
# Near-ties explored per lane (2**MAX_FLIPS trajectories at most).
MAX_FLIPS = 6

SUMMARY_KEYS = ("mean_latency", "mean_power_mw", "mean_energy",
                "mean_gateways", "mean_wavelengths", "saturated_frac",
                "total_reconfig_nj", "valid_intervals")
# The architectures this model describes, and the fields a sweep may vary.
ARCHS = ("resipi",)
SWEPT_FIELDS = ("l_m", "wavelengths", "n_chiplets")


def check_config(config: dict) -> None:
    """Refuse a configuration this model does not describe."""
    if config["arch"] not in ARCHS:
        raise ValueError(f"configuration arch {config['arch']!r}: this "
                         f"reference models {ARCHS} only")


class Precision:
    """Rounding applied after every arithmetic step."""

    def __init__(self, name: str = "f64"):
        if name not in ("f64", "bf16"):
            raise ValueError(f"unknown reference precision {name!r}")
        self.name = name
        if name == "bf16":
            import ml_dtypes
            self._bf16 = ml_dtypes.bfloat16

    def __call__(self, x):
        x = np.asarray(x, np.float64)
        if self.name == "f64":
            return x
        return x.astype(np.float32).astype(self._bf16).astype(np.float64)


# ---------------------------------------------------------------------------
# Traffic: the PARSEC-like trace definition
# ---------------------------------------------------------------------------

def parsec_draws(key, n_intervals: int, n_chiplets: int) -> dict:
    """The trace's random draws from one PRNG key: a phase offset, a
    [T, C] jitter normal and a [C] static chiplet-weight normal."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.device_put(key, jax.devices("cpu")[0])
        k_phase, k_jit, k_chip = jax.random.split(key, 3)
        u = jax.random.uniform(k_phase)
        z = jax.random.normal(k_jit, (n_intervals, n_chiplets))
        zc = jax.random.normal(k_chip, (n_chiplets,))
        return {"u": np.asarray(u, np.float64),
                "z": np.asarray(z, np.float64),
                "zc": np.asarray(zc, np.float64)}


def parsec_trace(profile: dict, draws: dict, q: Precision) -> dict:
    """ext/intra [T, C] and mem [T] loads for one application profile."""
    z, zc, u = q(draws["z"]), q(draws["zc"]), q(draws["u"])
    t = np.arange(z.shape[0], dtype=np.float64)
    arg = q(q(2.0 * np.pi * t / profile["phase_period"]) + q(u * 6.28))
    phase = q(1.0 + q(0.5 * q(np.sin(arg))))
    cv = profile["cv"]
    if cv > 0:
        sigma = q(np.sqrt(np.log1p(cv * cv)))
        jitter = q(np.exp(q(q(z * sigma) - q(0.5 * sigma * sigma))))
    else:
        jitter = np.ones_like(z)
    chip_w = q(np.clip(q(1.0 + q(0.15 * zc)), 0.7, 1.3))
    ext = q(q(q(profile["mean_ext_load"] * phase)[:, None] * jitter)
            * chip_w[None, :])
    frac = profile["ext_frac"]
    intra = q(ext * q((1.0 - frac) / max(frac, 1e-6)))
    mem = q(profile["mem_frac"] * q(np.sum(ext, axis=1)))
    return {"ext": ext, "intra": intra, "mem": mem}


# ---------------------------------------------------------------------------
# Selection tables (paper Sec. 3.4): balanced nearest-gateway partitions
# ---------------------------------------------------------------------------

def selection_levels(config: dict) -> Dict[str, np.ndarray]:
    """Mean router->gateway hops and mean access loss (dB) per activation
    level g = 1..G, from the mesh and the gateway positions of `config`.

    Each level partitions the routers into groups of at most ceil(R/g),
    each router joining its nearest active gateway, pairs taken in
    (distance, router, gateway) order."""
    mx, my = config["mesh_x"], config["mesh_y"]
    routers = [(x, y) for x in range(mx) for y in range(my)]
    gws = [tuple(p) for p in config["gateway_positions"]]
    gmax = config["max_gateways_per_chiplet"]
    hops, loss = [], []
    for g in range(1, gmax + 1):
        cap = -(-len(routers) // g)
        pairs = sorted((abs(rx - gx) + abs(ry - gy), r, j)
                       for r, (rx, ry) in enumerate(routers)
                       for j, (gx, gy) in enumerate(gws[:g]))
        assigned: Dict[int, int] = {}
        load = [0] * g
        for d, r, j in pairs:
            if r not in assigned and load[j] < cap:
                assigned[r] = d
                load[j] += 1
        hops.append(sum(assigned.values()) / len(routers))
        edge = [min(gx, mx - 1 - gx, gy, my - 1 - gy) for gx, gy in gws[:g]]
        db = [e * config["router_pitch_mm"]
              * config["power"]["waveguide_db_per_mm"] for e in edge]
        loss.append(sum(db) / g)
    return {"src_hops": np.asarray(hops), "gw_loss_db": np.asarray(loss)}


# ---------------------------------------------------------------------------
# The interval model (ReSiPI, uniform destinations)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Lanes:
    """L independent simulations, padded to a common chiplet axis C.

    ext/intra [L, T, C], mem [L, T], t_mask [L, T], chip_mask [L, C];
    l_m, wavelengths, max_gateways, min_gateways [L]."""
    ext: np.ndarray
    intra: np.ndarray
    mem: np.ndarray
    t_mask: np.ndarray
    chip_mask: np.ndarray
    l_m: np.ndarray
    wavelengths: np.ndarray
    max_gateways: np.ndarray
    min_gateways: np.ndarray

    def take(self, idx) -> "Lanes":
        return Lanes(**{f.name: getattr(self, f.name)[idx]
                        for f in dataclasses.fields(self)})


def _md1(rho, service, noc, q):
    r = q(np.clip(q(rho / noc["buffer_sat"]), 0.0, 0.995))
    return q(q(noc["burstiness"] * r * service) / q(2.0 * q(1.0 - r)))


def _access(hops, load, config, q):
    noc = config["noc"]
    pf = config["packet_flits"]
    walk = q(hops * noc["router_pipeline_cycles"])
    rho = q(np.clip(q(q(load * pf) / noc["feed_links"]), 0.0, 1.0))
    return q(walk + _md1(rho, float(pf), noc, q))


def _gateway(load, s_eff, config, q):
    noc = config["noc"]
    rho = q(np.clip(q(load * s_eff), 0.0, 1.0))
    return q(q(s_eff + _md1(rho, s_eff, noc, q))
             + noc["photonic_flight_cycles"])


def simulate(lanes: Lanes, config: dict, q: Precision = Precision(),
             flips: Optional[np.ndarray] = None,
             guide: Optional[np.ndarray] = None,
             record_g: bool = False) -> dict:
    """Run the interval model over every lane.

    `flips` [L, T, C] bool inverts the controller's decision at those
    points, where the point is a near-tie in this run; `guide` [L, T, C]
    (a program's per-interval gateway counts) picks the decision at each
    near-tie instead, where it is one of the two (see module doc).
    Returns the summaries (SUMMARY_KEYS, each [L]), the bounds that
    near-ties admit for saturated_frac (`sat_lo`, `sat_hi`) and
    total_reconfig_nj (`reconf_lo`, `reconf_hi`), and the near-tie points
    of this run (`near` [L, T, C] bool); with `record_g`, also the
    gateway count of every interval (`g_trace` [L, T, C])."""
    noc, pw = config["noc"], config["power"]
    n_l, n_t, n_c = lanes.ext.shape
    gmax = config["max_gateways_per_chiplet"]
    n_mem = config["memory_gateways"]
    levels = selection_levels(config)
    src_tab, loss_tab = q(levels["src_hops"]), q(levels["gw_loss_db"])
    mx, my = config["mesh_x"], config["mesh_y"]
    mesh_hops = q((mx * mx - 1) / (3.0 * mx) + (my * my - 1) / (3.0 * my))
    mesh_feed = 2.0 * mx
    interval = float(config["reconfig_interval_cycles"])
    chip = lanes.chip_mask.astype(np.float64)
    n_real = np.maximum(chip.sum(axis=1), 1.0)                      # [L]
    w = lanes.wavelengths.astype(np.float64)                        # [L]
    s_opt = q(config["packet_flits"] * config["flit_bits"]
              / q(w * config["link_gbps_per_wavelength"]
                  / config["noc_freq_ghz"]))
    s_eff = np.maximum(s_opt, float(config["packet_flits"]))        # [L]
    l_m = q(lanes.l_m.astype(np.float64))
    gmax_l = lanes.max_gateways.astype(np.int64)
    gmin_l = lanes.min_gateways.astype(np.int64)
    ctrl_mw = q((pw["controller_lgc_uw"] * n_real
                 + pw["controller_inc_uw"]) / 1000.0)
    per_lit_w = (pw["tia_mw"] + 2.0 * pw["tuning_mw_per_mr"]
                 + pw["driver_mw"])

    g = np.where(chip > 0, gmax_l[:, None], 0).astype(np.int64)     # [L, C]
    sums = {k: np.zeros(n_l) for k in ("latency", "power", "energy",
                                       "gateways", "wavelengths",
                                       "reconfig")}
    sat_lo = np.zeros(n_l)
    sat_hi = np.zeros(n_l)
    near = np.zeros((n_l, n_t, n_c), bool)
    g_trace = np.zeros((n_l, n_t, n_c), np.int64) if record_g else None
    for t in range(n_t):
        tv = lanes.t_mask[:, t].astype(np.float64)                  # [L]
        live = tv > 0
        e = q(q(lanes.ext[:, t, :] * tv[:, None]) * chip)
        it = q(q(lanes.intra[:, t, :] * tv[:, None]) * chip)
        m = q(lanes.mem[:, t] * tv)
        gf = np.maximum(g, 1).astype(np.float64)
        gw_load = q(e / gf)
        idx = np.maximum(g, 1) - 1
        src = src_tab[idx]
        mean_src = q(q(np.sum(q(src * chip), axis=1)) / n_real)
        access_db = q(q(np.sum(q(loss_tab[idx] * chip), axis=1)) / n_real)
        inter = q(q(_access(src, gw_load, config, q)
                    + _gateway(gw_load, s_eff[:, None], config, q))
                  + _access(mean_src[:, None], gw_load, config, q))
        inter = np.where(chip > 0, inter, 0.0)
        mem_gw = q(m / n_mem)
        mem_lat = q(q(_access(mean_src, mem_gw, config, q)
                      + _gateway(mem_gw, s_eff, config, q))
                    + _access(1.0, mem_gw, config, q))
        link = q(q(it * config["packet_flits"]) / mesh_feed)
        intra_lat = q(q(mesh_hops * noc["router_pipeline_cycles"]
                        + config["packet_flits"])
                      + _md1(q(np.clip(link, 0.0, 1.0)),
                             float(config["packet_flits"]), noc, q))
        tot_e = q(np.sum(e, axis=1) + 1e-9)
        tot_i = q(np.sum(it, axis=1) + 1e-9)
        tot_m = q(m + 1e-9)
        num = q(q(q(np.sum(q(inter * e), axis=1))
                  + q(np.sum(q(intra_lat * it), axis=1)))
                + q(mem_lat * tot_m))
        lat = q(q(num / q(q(tot_e + tot_i) + tot_m)) * tv)

        # Saturation: some gateway queue past the buffer knee.
        x = q(gw_load * s_eff[:, None])
        knee = noc["buffer_sat"]
        sat_lo += (np.any(x > knee * (1 + NEAR), axis=1) & live)
        sat_hi += (np.any(x > knee * (1 - NEAR), axis=1) & live)

        # Power: PCM-gated lit wavelengths (Sec. 3.2 / PROWAVES model).
        active = g.sum(axis=1) + n_mem                              # [L]
        lit_w = q(active * w)
        loss_scale = q(10.0 ** q(access_db / 10.0))
        total = q(q(q(q(lit_w * pw["laser_mw_per_wavelength"])
                      * loss_scale) + q(lit_w * per_lit_w)) + ctrl_mw)
        energy = q(total * lat)

        # Controller (Eqs. 5-7).
        load = q(q(e * interval) / q(interval * gf))
        thr_dn = q(l_m[:, None] * q(1.0 - q(1.0 / gf)))
        can_up = g < gmax_l[:, None]
        can_dn = g > gmin_l[:, None]
        up = (load > l_m[:, None]) & can_up
        dn = (load < thr_dn) & can_dn
        g_new = np.where(up, g + 1, np.where(dn, g - 1, g))
        tol = NEAR * l_m[:, None]
        near_up = can_up & (np.abs(load - l_m[:, None]) <= tol)
        near_dn = can_dn & (np.abs(load - thr_dn) <= tol)
        near_t = (near_up | near_dn) & (chip > 0) & live[:, None]
        near[:, t, :] = near_t
        alt = np.where(near_up, np.where(g_new == g, g + 1, g),
                       np.where(g_new == g, g - 1, g))
        if flips is not None:
            g_new = np.where(flips[:, t, :] & near_t, alt, g_new)
        if guide is not None and t + 1 < n_t:
            pick = guide[:, t + 1, :]
            ok = near_t & ((pick == g_new) | (pick == alt))
            g_new = np.where(ok, pick, g_new)

        # PCM reconfiguration energy: PCMCs whose coupling ratio changes.
        sw = _switched(g, g_new, gmax, n_mem, q)
        reconf = q(q(sw * pw["pcmc_reconfig_nj"]) * tv)
        if t + 1 == n_t and guide is not None:
            # The last decision is not recorded: admit every near-tie
            # there taken alone, or all of them.
            opts = [sw, _switched(g, np.where(near_t, alt, g_new), gmax,
                                  n_mem, q)]
            for i, c in np.argwhere(near_t):
                one = g_new.copy()
                one[i, c] = alt[i, c]
                opts.append(_switched(g, one, gmax, n_mem, q))
            last_lo = q(q(np.min(opts, axis=0) * pw["pcmc_reconfig_nj"])
                        * tv)
            last_hi = q(q(np.max(opts, axis=0) * pw["pcmc_reconfig_nj"])
                        * tv)
        else:
            last_lo = last_hi = reconf

        sums["latency"] += lat
        sums["power"] += q(total * tv)
        sums["energy"] += energy
        sums["gateways"] += g.sum(axis=1) * tv
        if record_g:
            g_trace[:, t, :] = g * live[:, None]
        sums["wavelengths"] += q(w * n_real) * tv
        sums["reconfig"] += reconf
        reconf_lo = (reconf_lo if t else 0.0) + last_lo
        reconf_hi = (reconf_hi if t else 0.0) + last_hi
        g = np.where(live[:, None], g_new, g)

    n_valid = lanes.t_mask.astype(np.float64).sum(axis=1)
    tt = np.maximum(n_valid, 1.0)
    out = {"mean_latency": sums["latency"] / tt,
           "mean_power_mw": sums["power"] / tt,
           "mean_energy": sums["energy"] / tt,
           "mean_gateways": sums["gateways"] / tt,
           "mean_wavelengths": sums["wavelengths"] / (tt * n_real),
           "saturated_frac": sat_lo / tt,
           "total_reconfig_nj": sums["reconfig"],
           "valid_intervals": n_valid}
    if q.name != "f64":
        out = {k: q(v) for k, v in out.items()}
    out["sat_lo"] = sat_lo / tt
    out["sat_hi"] = sat_hi / tt
    out["reconf_lo"] = np.minimum(reconf_lo, out["total_reconfig_nj"])
    out["reconf_hi"] = np.maximum(reconf_hi, out["total_reconfig_nj"])
    out["near"] = near
    if record_g:
        out["g_trace"] = g_trace
    return out


def _switched(g_old, g_new, gmax, n_mem, q):
    """PCMCs along the chain (chiplet-major slots, then the memory
    gateways) whose Eq. 4 coupling ratio 1/(GT - upstream) changes by
    more than 1e-6."""
    slot = np.arange(gmax)

    def kappa(g):
        act = (slot[None, None, :] < g[:, :, None]).reshape(g.shape[0], -1)
        act = np.concatenate(
            [act, np.ones((g.shape[0], n_mem), bool)], axis=1)
        a = act.astype(np.float64)
        gt = a.sum(axis=1, keepdims=True)
        up = np.cumsum(a, axis=1) - a
        return np.where(a[:, :-1] > 0,
                        q(1.0 / np.maximum(gt - up, 1.0))[:, :-1], 0.0)

    return (np.abs(kappa(g_new) - kappa(g_old)) > 1e-6).sum(axis=1)


def simulate_guided(lanes: Lanes, config: dict, guide: np.ndarray,
                    q: Precision = Precision()) -> List[dict]:
    """One trajectory per lane, near-ties decided by `guide`, in the
    per-lane form `simulate_admissible` returns."""
    keys = SUMMARY_KEYS + ("sat_lo", "sat_hi", "reconf_lo", "reconf_hi")
    out = simulate(lanes, config, q, guide=guide)
    return [{k: np.asarray([out[k][i]]) for k in keys}
            for i in range(lanes.ext.shape[0])]


def simulate_admissible(lanes: Lanes, config: dict,
                        q: Precision = Precision()) -> List[dict]:
    """The natural run plus, for lanes with near-ties, the runs with
    those decisions inverted (every subset of the first MAX_FLIPS
    near-ties). Returns one summary dict per lane, each holding arrays
    over that lane's admissible trajectories ([V])."""
    bounds = ("sat_lo", "sat_hi", "reconf_lo", "reconf_hi")
    base = simulate(lanes, config, q)
    per_lane = [[{k: base[k][i] for k in SUMMARY_KEYS + bounds}]
                for i in range(lanes.ext.shape[0])]
    src, flip_rows = [], []
    for i in np.flatnonzero(base["near"].any(axis=(1, 2))):
        pts = np.argwhere(base["near"][i])[:MAX_FLIPS]
        for r in range(1, len(pts) + 1):
            for sub in itertools.combinations(range(len(pts)), r):
                f = np.zeros(base["near"].shape[1:], bool)
                for s in sub:
                    f[tuple(pts[s])] = True
                src.append(i)
                flip_rows.append(f)
    if src:
        alt = simulate(lanes.take(np.asarray(src)), config, q,
                       flips=np.stack(flip_rows))
        for j, i in enumerate(src):
            per_lane[i].append({k: alt[k][j] for k in
                                SUMMARY_KEYS + bounds})
    return [{k: np.asarray([v[k] for v in lane]) for k in lane[0]}
            for lane in per_lane]


# ---------------------------------------------------------------------------
# The comparison that decides `correct`
# ---------------------------------------------------------------------------

def key_scales(admissible: List[dict]) -> Dict[str, float]:
    """Median |reference| per key over the lanes' natural trajectories."""
    out = {}
    for k in SUMMARY_KEYS:
        med = float(np.median([abs(float(a[k][0])) for a in admissible]))
        out[k] = med if med > 0 else 1.0
    return out


def lane_gaps(program: Dict[str, np.ndarray],
              admissible: List[dict]) -> tuple:
    """(gap [L], worst key per lane) of program lanes against their
    admissible reference trajectories. `program[k]` is [L]."""
    scales = key_scales(admissible)
    n = len(admissible)
    gaps = np.zeros(n)
    worst = [""] * n
    for i, adm in enumerate(admissible):
        best, best_key = np.inf, "no trajectory"
        for v in range(len(adm["mean_latency"])):
            g, gk = 0.0, ""
            for k in SUMMARY_KEYS:
                p = float(program[k][i])
                if not np.isfinite(p):
                    g, gk = np.inf, k
                    break
                if k == "saturated_frac":
                    lo, hi = float(adm["sat_lo"][v]), float(adm["sat_hi"][v])
                    d = max(0.0, lo - p, p - hi)
                elif k == "total_reconfig_nj":
                    lo = float(adm["reconf_lo"][v])
                    hi = float(adm["reconf_hi"][v])
                    d = max(0.0, lo - p, p - hi) / max(lo, scales[k])
                else:
                    r = float(adm[k][v])
                    d = abs(p - r) / max(abs(r), scales[k])
                if d > g:
                    g, gk = d, k
            if g < best:
                best, best_key = g, gk
        gaps[i], worst[i] = best, best_key
    return gaps, worst
