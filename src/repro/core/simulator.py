"""Epoch-level 2.5D network simulator of the ReSiPI interposer (PAPER.md).

Simulates the four compared interposer architectures (§4.1) over a traffic
trace, one `lax.scan` step per reconfiguration interval:

  * RESIPI      — dynamic gateways (Eqs. 5-7), 4 wavelengths, PCM gating
  * RESIPI_ALL  — ReSiPI datapath with all gateways always active (Fig. 11)
  * PROWAVES    — 1 gateway/chiplet, dynamic wavelength count [16]
  * AWGR        — 4 gateways/chiplet static, 1 wavelength/port, 1.8 dB loss [8]

Each step: traffic -> per-gateway load (selection tables) -> latency
(noc.NocModel) -> power (photonics.interposer_power_mw) -> controller update.
Energy is reported as power x mean-packet-latency (per-packet service-energy
proxy) — consistent with the paper (PAPER.md), whose -53% energy claim is the
product of its -37% latency and -25% power claims.

Engine model (compile-once, batch-everywhere):

  * `SimConfig` (and the nested `NetworkConfig` / `ControllerConfig` /
    `NocModel`) are frozen dataclasses, hence hashable, and are passed to
    `jax.jit` as *static* arguments: equal configs hit the compile cache,
    distinct configs get their own executable.
  * `simulate`       — single trace, jit-cached on (trace shape, config).
  * `simulate_batch` — N stacked traces, one vmapped scan per config.
  * `sweep`          — vmap over *runtime* scalar overrides (`l_m`,
    `buffer_sat`, `wavelengths`, `prowaves_rho_hi/lo`) so a DSE over K
    parameter values is one compilation, not K.
  * `sweep_topology` / `sweep_topology_batch` — vmap over *shape-changing*
    topology axes (`n_chiplets`, `gateways_per_chiplet`, `mesh_radix`) via
    pad-to-max batching with validity masks: a hundreds-of-chiplets scan
    is ONE compiled executable, and padded slots provably contribute zero
    load/latency/power (see ROADMAP.md "Topology-sweep API").
  * `shard_sweep`    — the same padded grid with its topology axis sharded
    across devices (NamedSharding/GSPMD), single-device fallback.
  * `sweep_placement` / `sweep_placement_batch` — vmap K candidate gateway
    *placements* (NetworkConfig.gateway_positions) through the same ONE
    compiled masked scan; placements enter purely as traced hop/loss
    tables, so a placement DSE never recompiles per candidate.
  * `search_placement` — PlaceIT-style greedy/annealed placement search.
    The default engine is DEVICE-RESIDENT (repro.core.search): proposals,
    traceable placement tables, scoring, annealed acceptance and history
    run inside ONE compiled `lax.scan` — a whole search is a single
    dispatch. `engine="host"` keeps the PR-3 numpy-proposal loop (one
    `sweep_placement` call per generation) as the parity oracle.
  * `search_placement_islands` — K independent annealed chains vmapped
    over seeds in the same single executable; runtime `SWEEPABLE_FIELDS`
    grids of length K zip with the island axis (joint placement x
    runtime-knob search), sharded across devices when available.
  * `sweep_workload` — K `traffic.TrafficSpec` workloads (mixed lengths
    allowed) generated from seeds and run as ONE compiled executable;
    runtime/topology/placement grids of the same length zip in.
  * **Ragged time axis** — every batched entry point accepts mixed-length
    traces: `stack_traces(..., pad=True)` pads to the longest T with a
    `t_mask`, and masked tail intervals provably contribute zero to every
    latency/power/energy reduction (padded lane == unpadded `simulate`,
    pinned per-arch in tests — the time-axis analogue of the PR 2
    chiplet-masking invariant).
  * `SimSession.init(sim)` / `session.step_chunk(chunk)` — streaming
    simulation with a donated carry: controller/PROWAVES state persists
    across chunks, so an unbounded online trace runs at fixed memory and
    a chunked run bit-matches the one-shot `simulate` records.
  * `engine_stats()` — trace/compile counters used by tests and benches.

`simulate_eager` preserves the pre-engine per-call retrace path for
benchmark baselines (benchmarks/bench_engine.py).
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import photonics, topology, traffic
from repro.core.faults import FAULT_KEYS, stack_fault_frames
from repro.core.constants import (NETWORK, PROWAVES_MAX_WAVELENGTHS,
                                  PROWAVES_MIN_WAVELENGTHS,
                                  RESIPI_WAVELENGTHS, NetworkConfig,
                                  PHOTONIC_POWER)
from repro.core.gateway_controller import (ControllerConfig, ControllerState,
                                           epoch_step)
from repro.core.noc import NocModel, uniform_mesh_mean_hops
from repro.core.selection import (N_DEFAULT_EDGE_SLOTS,
                                  build_selection_tables, mean_access_hops,
                                  normalize_placement,
                                  padded_selection_tables_jax,
                                  resolve_gateway_positions,
                                  selection_tables_jax)
from repro.core.traffic.transform import _as_array, _xp
from repro.runtime import spans


class Arch(enum.Enum):
    RESIPI = "resipi"
    RESIPI_ALL = "resipi_all"
    PROWAVES = "prowaves"
    AWGR = "awgr"


@dataclasses.dataclass(frozen=True)
class SimConfig:
    arch: Arch = Arch.RESIPI
    cfg: NetworkConfig = NETWORK
    ctl: ControllerConfig = ControllerConfig()
    noc: NocModel = NocModel()
    wavelengths: int = RESIPI_WAVELENGTHS
    # PROWAVES wavelength controller: multiplicative increase/decrease with
    # utilization hysteresis (reactive approximation of [16]'s epoch policy).
    prowaves_rho_hi: float = 0.5
    prowaves_rho_lo: float = 0.30
    # Run the interval-scan body as the fused `kernels.epoch_step` Pallas
    # kernel (interpret on CPU, compiled on TPU) instead of the XLA lax.scan
    # body. Applies to the RESIPI/RESIPI_ALL unpadded-topology paths; other
    # configurations run the scan body (engine_stats()["kernel_traces"]
    # counts the traces that took the kernel), which doubles as the
    # kernel's 1e-6 parity oracle (kernels/epoch_step/ref.py).
    epoch_kernel: bool = False

    def with_arch(self, arch: Arch) -> "SimConfig":
        w = {Arch.RESIPI: RESIPI_WAVELENGTHS,
             Arch.RESIPI_ALL: RESIPI_WAVELENGTHS,
             Arch.PROWAVES: PROWAVES_MAX_WAVELENGTHS,
             Arch.AWGR: 1}[arch]
        # PROWAVES ships 32-flit gateway buffers (4x ReSiPI, Table 1): deeper
        # buffers push the backpressure knee out.
        noc = dataclasses.replace(self.noc,
                                  buffer_sat=0.65 if arch == Arch.PROWAVES
                                  else self.noc.buffer_sat)
        return dataclasses.replace(self, arch=arch, wavelengths=w, noc=noc)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SimState:
    ctl: ControllerState          # gateway controller (ReSiPI)
    wavelengths: jax.Array        # [C] PROWAVES per-chiplet active lambdas
    prev_active: jax.Array        # [N_total] previous gateway activity


def _activity_mask(g: jax.Array, sim: SimConfig) -> jax.Array:
    """Expand per-chiplet g into the global gateway-chain activity mask.

    Chain layout: C chiplets x G gateway slots (activation order), then the
    2 memory-controller gateways, which are always active (Table 1).
    """
    gmax = sim.cfg.max_gateways_per_chiplet
    slots = jnp.arange(gmax)[None, :] < g[:, None]          # [C, G]
    mem = jnp.ones((sim.cfg.memory_gateways,), bool)
    return jnp.concatenate([slots.reshape(-1), mem])


def _interval_metrics(g: jax.Array, wavelengths: jax.Array,
                      ext_load: jax.Array, mem_load: jax.Array,
                      int_load: jax.Array, ext_frac: jax.Array,
                      sim: SimConfig, tables: dict,
                      topo: Optional[dict] = None,
                      t_valid: jax.Array | float = 1.0,
                      extra_db: Optional[jax.Array] = None,
                      dest: Optional[jax.Array] = None) -> dict:
    """Latency/load metrics for one interval given activity (g, lambda).

    With `topo` (the padded topology-sweep path) the chiplet axis is padded
    to the grid maximum: every reduction is mask-weighted so padded chiplet
    lanes contribute exactly zero load/latency, and the per-topology hop
    tables/mesh scalars come from `topo` instead of the static config.

    `t_valid` is the interval's time-validity bit (ragged-T padding): a
    masked interval carries zero injected load already, but zero-load
    latency is NOT zero (the memory term alone yields a finite quotient),
    so every returned metric is multiplied by `t_valid` — a padded tail
    interval contributes exactly zero to every downstream reduction.

    `extra_db` (fault path) is the interval's optical loss-drift term,
    added to the placement's access loss so the laser power manager
    compensates for device aging; None (and the 0.0 a never-firing fault
    frame compiles to) leaves the fault-free math bit-identical.

    `dest` (destination-aware path) is the trace's row-stochastic [C, C]
    destination matrix: the destination leg of each inter-chiplet packet is
    then priced at the *actual* destination's gateway pressure (received
    load over its active gateways, with a fan-in concentration factor on
    the ejection queueing) instead of the uniform-destination mean-hop
    approximation. `dest=None` keeps the pre-dest math verbatim —
    bit-identical numbers for every existing trace.
    """
    noc = sim.noc
    # Per-gateway load after the Fig. 8 balanced selection. ext traffic of a
    # chiplet spreads over its g active gateways; memory traffic over the 2
    # memory gateways.
    gw_load = ext_load / jnp.maximum(g.astype(jnp.float32), 1.0)       # [C]
    mem_gw_load = mem_load / sim.cfg.memory_gateways

    if topo is None:
        chip_mask = None
        src_hops = mean_access_hops(tables, g)                         # [C]
        mean_src_hops = jnp.mean(src_hops)
        # Placement-derived optical access loss at each chiplet's current
        # activation level (0 dB for the default edge scheme).
        access_db = jnp.mean(tables["gw_loss_db"][jnp.maximum(g, 1) - 1])
        lam = wavelengths
        lam_mem = wavelengths if wavelengths.ndim == 0 \
            else jnp.mean(wavelengths)
        mesh_hops = jnp.float32(uniform_mesh_mean_hops(sim.cfg))
        # Rows feeding the gateway cut: mesh_x on a derived mesh (the
        # pre-coords constant, bit parity), sqrt(R) on explicit layouts.
        mesh_feed = 2.0 * topology.feed_width(sim.cfg)
    else:
        chip_mask = topo["chip_mask"]                                  # [C]
        src_hops = topo["src_hops"][jnp.maximum(g, 1) - 1]             # [C]
        nreal = jnp.maximum(jnp.sum(chip_mask), 1.0)
        mean_src_hops = jnp.sum(src_hops * chip_mask) / nreal
        gdb = topo["gw_loss_db"][jnp.maximum(g, 1) - 1]                # [C]
        access_db = jnp.sum(gdb * chip_mask) / nreal
        # Padded chiplet lanes carry lambda=0; clamp inside the latency math
        # only (their latencies are masked to zero below) so serialization
        # never divides by zero.
        lam = wavelengths if wavelengths.ndim == 0 \
            else jnp.where(chip_mask > 0, wavelengths, 1.0)
        lam_mem = wavelengths if wavelengths.ndim == 0 \
            else jnp.sum(wavelengths * chip_mask) / nreal
        mesh_hops = topo["mesh_hops"]
        mesh_feed = 2.0 * topo["mesh_x"]
    if extra_db is not None:
        access_db = access_db + extra_db

    if dest is None:
        # Destination side: packets land on a uniformly random other chiplet;
        # the destination hop count mixes the other chiplets' activation
        # levels.
        dst_hops = mean_src_hops * jnp.ones_like(src_hops)
        inter_lat = noc.inter_chiplet_latency(gw_load, lam,
                                              src_hops, dst_hops)      # [C]
    else:
        # Destination-aware: resolve the actual source->destination gateway
        # pressure. recv_j is the load *received* by chiplet j; phi_j is the
        # fan-in concentration (inverse participation ratio of the arrival
        # mix — 1 for a single-source permutation, ~1/(C-1) for uniform),
        # which scales the ejection queue's effective burstiness: one
        # dominant source is a near-deterministic arrival process, many
        # interleaved sources keep the full batch factor.
        w_ij = ext_load[:, None] * dest                            # [C, C]
        recv = jnp.sum(w_ij, axis=0)                               # [C]
        phi = jnp.sum(w_ij * w_ij, axis=0) / jnp.maximum(recv * recv, 1e-12)
        burst_scale = (1.0 + (noc.burstiness - 1.0) * phi) / noc.burstiness
        dst_gw_load = recv / jnp.maximum(g.astype(jnp.float32), 1.0)  # [C]
        dst_leg = noc.access_latency(src_hops, dst_gw_load, burst_scale)
        if chip_mask is not None:
            dst_leg = jnp.where(chip_mask > 0, dst_leg, 0.0)
        inter_lat = (noc.access_latency(src_hops, gw_load)
                     + noc.gateway_latency(gw_load, lam)
                     + jnp.matmul(dest, dst_leg,      # full f32 on TPU too
                                  precision=jax.lax.Precision.HIGHEST))  # [C]
    if chip_mask is not None:
        inter_lat = jnp.where(chip_mask > 0, inter_lat, 0.0)
    mem_lat = noc.inter_chiplet_latency(mem_gw_load, lam_mem,
                                        mean_src_hops, 1.0)
    link_load = int_load * sim.cfg.packet_flits / mesh_feed
    intra_lat = noc.mesh_latency(mesh_hops, link_load)                 # [C]

    # Traffic-weighted average packet latency across chiplets + memory.
    # (In the padded path ext/int loads of padded chiplets are zero, so
    # every weighted term below is mask-correct by construction.)
    w_ext = ext_load
    tot_ext = jnp.sum(w_ext) + 1e-9
    tot_int = jnp.sum(int_load) + 1e-9
    tot_mem = mem_load + 1e-9
    lat = (jnp.sum(inter_lat * w_ext) + jnp.sum(intra_lat * int_load)
           + mem_lat * tot_mem) / (tot_ext + tot_int + tot_mem)
    out = {"latency": lat * t_valid, "gw_load": gw_load * t_valid,
           "inter_latency": inter_lat * t_valid,
           "mean_inter_latency": jnp.sum(inter_lat * w_ext) / tot_ext
                                 * t_valid,
           "access_db": access_db,
           "saturated": jnp.any(noc.saturated(gw_load, lam))
                        & (t_valid > 0)}
    if dest is not None:
        # Raw (un-time-masked, like ext_load itself): the controller's
        # pressure term consumes it inside the same step.
        out["recv_load"] = recv
    return out


def _prowaves_update(lam: jax.Array, inter_latency: jax.Array,
                     gw_load: jax.Array, sim: SimConfig) -> jax.Array:
    """PROWAVES wavelength adaptation: latency-target driven [16].

    PROWAVES picks the wavelength count that keeps the experienced network
    delay under a target derived from the zero-load latency. When the single
    gateway's electronic port is the bottleneck, extra wavelengths cannot
    reduce delay, so the controller ratchets to the maximum and stays there
    (the Fig. 12.d behavior) — power burns while latency stays high.
    Multiplicative up / down with hysteresis reproduces the ~5-interval
    instability on load transitions reported in §4.5.
    """
    base = sim.noc.inter_chiplet_latency(
        jnp.float32(1e-4), jnp.float32(PROWAVES_MAX_WAVELENGTHS),
        jnp.float32(2.5), jnp.float32(2.5))
    s = sim.noc.serialization_cycles(lam)
    rho_opt = gw_load * s
    lam_up = jnp.minimum(lam * 2, PROWAVES_MAX_WAVELENGTHS)
    lam_dn = jnp.maximum(lam // 2, PROWAVES_MIN_WAVELENGTHS)
    hot = inter_latency > 1.5 * base
    cold = (inter_latency < 1.3 * base) & (rho_opt < sim.prowaves_rho_lo)
    return jnp.where(hot, lam_up, jnp.where(cold, lam_dn, lam))


def make_step(sim: SimConfig, tables: dict, topo: Optional[dict] = None,
              faulted: bool = False, dest: Optional[jax.Array] = None):
    """Build the per-interval scan body for the chosen architecture.

    `topo` switches on the padded topology-sweep path: the chiplet/gateway
    axes are padded to the grid maximum, `topo["chip_mask"]` marks the real
    chiplets, and the per-topology scalars (actual gateway totals, mesh
    geometry, hop tables) are traced values. Padded chiplet lanes hold g=0
    and lambda=0 throughout, so activity masks, power sums, and reconfig
    energy see them as permanently dark gateways.

    `faulted` appends the fault-frame xs (gw_ok [C, G], stuck_on [C, G],
    drift_db scalar — see repro.core.faults): a failed gateway slot is a
    dead lane exactly like a padded one — it carries no traffic (the
    chiplet's capacity drops to the surviving slots), draws no power and
    charges no reconfig energy — while a stuck-on cell burns power the
    controller cannot gate, and drift_db erodes the optical budget. An
    all-healthy frame reproduces the fault-free step bit-for-bit, so the
    fault executables share every masking invariant with the clean ones.

    `dest` is the trace's optional [C, C] destination matrix, a per-trace
    constant closed over the step (not a per-interval xs): it re-prices the
    destination leg in `_interval_metrics` and feeds the gateway controller
    a received-load pressure term, so congestion-aware deployment reacts to
    where packets actually *land*. `dest=None` is the pre-dest step,
    bit-for-bit.
    """
    cfg, ctl_cfg = sim.cfg, sim.ctl
    interval = float(cfg.reconfig_interval_cycles)
    n_total = cfg.total_gateways
    gmax = cfg.max_gateways_per_chiplet
    chip_mask = None if topo is None else topo["chip_mask"]
    # Actual (traced) counts for count-dependent power terms; None selects
    # the static-config behavior on the unpadded path.
    gw_count = None if topo is None else topo["total_gateways"]
    n_chips = cfg.n_chiplets if topo is None else topo["n_chiplets"]

    def _lit_mask(g_des: jax.Array, gw_ok: jax.Array,
                  stuck_on: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """(usable [C, G], powered chain [N_total] bool) under faults.

        usable = slots the controller wants AND whose hardware works;
        powered = usable OR stuck-on-but-working (a lane the PCM cannot
        darken still burns laser/ring power); memory gateways are always
        on. A failed slot is in neither — provably dark and dead.
        """
        desired = (jnp.arange(gmax)[None, :]
                   < g_des[:, None]).astype(jnp.float32)        # [C, G]
        usable = desired * gw_ok
        lit = jnp.maximum(usable, stuck_on * gw_ok)
        mem_on = jnp.ones((cfg.memory_gateways,), jnp.float32)
        return usable, jnp.concatenate([lit.reshape(-1), mem_on]) > 0.5

    def step(state: SimState, tr) -> Tuple[SimState, dict]:
        ext, mem, intra, ext_frac, t_valid = tr[:5]
        if faulted:
            gw_ok, stuck_on, drift_db = tr[5:]
        else:
            gw_ok = stuck_on = drift_db = None
        if sim.arch in (Arch.RESIPI, Arch.RESIPI_ALL):
            g = state.ctl.g
            lam = jnp.float32(sim.wavelengths)
        elif sim.arch == Arch.PROWAVES:
            g = jnp.ones((cfg.n_chiplets,), jnp.int32) if topo is None \
                else (chip_mask > 0).astype(jnp.int32)
            lam = state.wavelengths.astype(jnp.float32)
        else:  # AWGR: all gateways, 1 lambda per port
            g = jnp.full((cfg.n_chiplets,), cfg.max_gateways_per_chiplet,
                         jnp.int32) if topo is None \
                else jnp.where(chip_mask > 0,
                               topo["g_max"].astype(jnp.int32), 0)
            lam = jnp.float32(1.0)

        # Fault-effective capacity: the chiplet only has its usable active
        # slots; g_eff == g whenever the frame never fires (exact parity).
        if faulted:
            usable, active_eff = _lit_mask(g, gw_ok, stuck_on)
            g_eff = jnp.sum(usable, axis=1).astype(jnp.int32)
        else:
            g_eff = g

        m = _interval_metrics(g_eff, lam, ext, mem, intra, ext_frac, sim,
                              tables, topo, t_valid=t_valid,
                              extra_db=drift_db, dest=dest)

        # --- power ---------------------------------------------------------
        active = active_eff if faulted else _activity_mask(g, sim)
        if sim.arch == Arch.PROWAVES:
            # 1 lit gateway per chiplet + memory gateways, per-chiplet
            # lambdas. Padded chiplet lanes carry lambda=0, so the "wdm"
            # power sums are mask-correct without further masking.
            n_pw = cfg.n_chiplets + cfg.memory_gateways
            w = state.wavelengths.astype(jnp.float32)
            if faulted:
                # A failed PROWAVES gateway (slot 0 is the chiplet's only
                # one) takes its lasers down with it: lambda * gw_ok is 0
                # for dead chiplets, identity for healthy ones.
                w = w * gw_ok[:, 0]
            if topo is None:
                lam_mem_val = jnp.mean(w)
            else:
                lam_mem_val = jnp.sum(w) / jnp.maximum(
                    jnp.sum(chip_mask), 1.0)
            lam_mem = jnp.full((cfg.memory_gateways,), lam_mem_val)
            per_gw_lam = jnp.concatenate([w, lam_mem])
            pw = photonics.interposer_power_mw(
                jnp.ones((n_pw,), bool), per_gw_lam,
                n_gateways=n_pw, mode="wdm", loss_db=m["access_db"],
                n_chiplets=n_chips)
        elif sim.arch == Arch.AWGR:
            # One wavelength per provisioned port (18 total in Table 1);
            # padded lanes are inactive, so summing the activity mask keeps
            # the laser/filter counts at the topology's real port count.
            pw = photonics.interposer_power_mw(
                active, active.astype(jnp.float32),
                n_gateways=n_total,
                loss_db=PHOTONIC_POWER.awgr_loss_db + m["access_db"],
                mode="static", gateway_count=gw_count, n_chiplets=n_chips)
        else:
            pw = photonics.interposer_power_mw(
                active, jnp.float32(sim.wavelengths),
                n_gateways=n_total, mode="pcm", loss_db=m["access_db"],
                n_chiplets=n_chips)

        # --- controller update ----------------------------------------------
        reconf_nj = jnp.float32(0.0)
        if sim.arch == Arch.RESIPI:
            if dest is None:
                pressure = ext
            else:
                # Destination-aware deployment pressure: a gateway group
                # serves both the chiplet's injected and received packets,
                # so the controller meters the hotter of the two — transpose
                # hot-destinations activate spares even though their own
                # injection is modest.
                pressure = jnp.maximum(ext, m["recv_load"])
            packets = pressure * interval
            if faulted:
                # The controller meters load per USABLE gateway: failures
                # concentrate the same packets on fewer lanes, so the
                # measured load rises and the hysteresis law activates
                # spares on its own (epoch_step divides by the desired g,
                # hence the g/g_eff rescale; exactly 1.0 when healthy).
                packets = packets * (g.astype(jnp.float32)
                                     / jnp.maximum(
                                         g_eff.astype(jnp.float32), 1.0))
            new_ctl, rec = epoch_step(state.ctl, packets, interval, ctl_cfg)
            if faulted:
                _, new_active = _lit_mask(new_ctl.g, gw_ok, stuck_on)
            else:
                new_active = _activity_mask(new_ctl.g, sim)
            reconf_nj = photonics.reconfig_energy_nj(active, new_active)
            new_state = SimState(ctl=new_ctl, wavelengths=state.wavelengths,
                                 prev_active=new_active)
        elif sim.arch == Arch.PROWAVES:
            lam_new = _prowaves_update(state.wavelengths,
                                       m["inter_latency"], m["gw_load"], sim)
            if chip_mask is not None:
                # Keep padded chiplet lanes at lambda=0 explicitly: the
                # controller's `cold` branch would otherwise ratchet a dead
                # lane up to the minimum wavelength floor, and the "wdm"
                # power sums are unmasked by design.
                lam_new = jnp.where(chip_mask > 0, lam_new, 0)
            new_state = SimState(ctl=state.ctl, wavelengths=lam_new,
                                 prev_active=active)
        else:
            new_state = SimState(ctl=state.ctl, wavelengths=state.wavelengths,
                                 prev_active=active)

        # energy proxy: mW * cycles-per-packet -> pJ-scale unit (model units)
        # (latency is already t_valid-masked, so energy is too.)
        energy = pw["total_mw"] * m["latency"]
        lam_rec = lam * jnp.ones((cfg.n_chiplets,)) if topo is None \
            else lam * chip_mask
        # Time-mask every record: a padded tail interval must read as zero
        # gateways / zero power / zero reconfig energy, never as an idle but
        # powered network — the t-axis analogue of the chiplet masking.
        rec = {"latency": m["latency"], "power_mw": pw["total_mw"] * t_valid,
               "laser_mw": pw["laser_mw"] * t_valid, "energy": energy,
               "reconfig_nj": reconf_nj * t_valid,
               # "g" reports the EFFECTIVE gateway count (usable active
               # slots): failed slots count zero in every reduction, like
               # padded ones. g_eff == g on every fault-free path.
               "g": g_eff * t_valid.astype(g_eff.dtype),
               "wavelengths": lam_rec * t_valid,
               "gw_load": m["gw_load"],
               "mean_inter_latency": m["mean_inter_latency"],
               "saturated": m["saturated"]}
        if faulted:
            # Fault telemetry (fault executables only — extra record keys
            # never feed _record_sums): the controller's desired g and the
            # count of desired-but-dead slots per interval.
            rec["g_desired"] = g * t_valid.astype(g.dtype)
            rec["failed_slots"] = (jnp.sum(
                (jnp.arange(gmax)[None, :] < g[:, None]) * (gw_ok < 0.5))
                .astype(jnp.float32) * t_valid)
        # Masked intervals FREEZE the carry (like the noc_step kernel's
        # frozen cycles): the controller must not react to the fake idle
        # epochs of a padded gap, so a mask-interior gap — a mid-stream
        # padded chunk, a concat of padded traces — resumes exactly where
        # the last valid interval left off.
        new_state = jax.tree.map(
            lambda new, old: jnp.where(t_valid > 0, new, old),
            new_state, state)
        return new_state, rec

    return step


# ---------------------------------------------------------------------------
# Engine core
# ---------------------------------------------------------------------------

# Trace-time counters: bumped every time jax actually traces a simulation
# body. A warm jit cache leaves these untouched — tests/benches assert on it.
# `search_dispatches` counts device-resident search executable launches
# (repro.core.search): one whole annealed search == one dispatch.
# `kernel_traces` counts the traces that took the fused epoch_step kernel,
# so which body a run used is observable, not assumed.
_STATS = {"traces": 0, "search_dispatches": 0, "kernel_traces": 0}

# Config fields that `sweep` may override with runtime (traced) scalars.
# All are scalar knobs that feed jnp comparisons/arithmetic — nothing that
# changes array shapes (max_gateways/min_gateways clamp the controller; the
# gateway-slot axis is still sized by the static max_gateways_per_chiplet).
SWEEPABLE_FIELDS = ("l_m", "buffer_sat", "wavelengths",
                    "prowaves_rho_hi", "prowaves_rho_lo",
                    "max_gateways", "min_gateways")

# Shape-defining topology axes that `sweep_topology` batches via pad-to-max:
# every grid point is padded to the grid maxima (chiplets, gateway slots,
# routers) and carried through ONE compiled executable with validity masks.
# `gateway_positions` is the placement axis (PlaceIT-style DSE): each grid
# value is a placement — a tuple of (x, y) router coordinates in activation
# order, or None for the default edge scheme — and enters the executable
# purely through traced per-point tables (src_hops / gw_loss_db), so K
# placements never cost K compiles.
TOPOLOGY_SWEEPABLE_FIELDS = ("n_chiplets", "gateways_per_chiplet",
                             "mesh_radix", "gateway_positions")


def engine_stats() -> dict:
    """Engine instrumentation: scan-body trace count + table-cache stats."""
    info = build_selection_tables.cache_info()
    return {"simulate_traces": _STATS["traces"],
            "kernel_traces": _STATS["kernel_traces"],
            "search_dispatches": _STATS["search_dispatches"],
            "selection_table_builds": info.misses,
            "selection_table_hits": info.hits}


def reset_engine_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def clear_engine_caches() -> None:
    """Drop every jit executable the engine holds (cold-start measurement).

    The single place that knows all jitted entry points — benches must use
    this instead of reaching for the private wrappers, so adding an entry
    point can't silently leave a warm cache in a 'cold' measurement.
    """
    from repro.core.pareto import clear_codesign_caches
    from repro.core.search import clear_search_caches
    from repro.core.traffic.dest import clear_destination_caches

    for f in (_simulate_jit, _sweep_jit, _sweep_batch_jit,
              _sweep_topology_jit, _sweep_topology_batch_jit,
              _sweep_workload_jit, _sweep_workload_topo_jit,
              _session_chunk_jit, _session_tick_jit):
        f.clear_cache()
    clear_search_caches()
    clear_codesign_caches()
    clear_destination_caches()


def _grid_len(name: str, values) -> int:
    """Length of one swept grid, rejecting scalars with a clear message."""
    if name == "gateway_positions":
        if not isinstance(values, (list, tuple)):
            raise ValueError(
                f"swept field {name!r} must be a list of placements "
                f"(each a tuple of (x, y) pairs or None), got "
                f"{type(values).__name__}")
        return len(values)
    try:
        arr = jnp.asarray(values)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"swept field {name!r} must be a numeric grid "
            f"({e})") from None
    if arr.ndim != 1:
        raise ValueError(
            f"swept field {name!r} must be a 1-D grid of values, got "
            f"shape {arr.shape} — wrap a single value as [{name}_value]")
    return int(arr.shape[0])


def _apply_overrides(sim: SimConfig, ov: Optional[Dict[str, jax.Array]]
                     ) -> SimConfig:
    """Graft runtime override scalars into a (traced) config copy.

    The returned SimConfig holds tracers and must never be hashed / used as
    a static jit argument — it only flows through the scan body.
    """
    if not ov:
        return sim
    unknown = set(ov) - set(SWEEPABLE_FIELDS)
    if unknown:
        raise ValueError(f"non-sweepable fields: {sorted(unknown)} "
                         f"(sweepable: {SWEEPABLE_FIELDS})")
    ctl_over = {k: ov[k] for k in ("l_m", "max_gateways", "min_gateways")
                if k in ov}
    if ctl_over:
        sim = dataclasses.replace(sim, ctl=dataclasses.replace(
            sim.ctl, **ctl_over))
    if "buffer_sat" in ov:
        sim = dataclasses.replace(sim, noc=dataclasses.replace(
            sim.noc, buffer_sat=ov["buffer_sat"]))
    if "wavelengths" in ov:
        sim = dataclasses.replace(sim, wavelengths=ov["wavelengths"])
    if "prowaves_rho_hi" in ov:
        sim = dataclasses.replace(sim, prowaves_rho_hi=ov["prowaves_rho_hi"])
    if "prowaves_rho_lo" in ov:
        sim = dataclasses.replace(sim, prowaves_rho_lo=ov["prowaves_rho_lo"])
    return sim


def _initial_state(sim: SimConfig) -> SimState:
    """Fresh unpadded simulation state (shared by `simulate`/`SimSession`)."""
    cfg = sim.cfg
    return SimState(
        ctl=ControllerState.init(cfg.n_chiplets, sim.ctl),
        wavelengths=jnp.full((cfg.n_chiplets,), PROWAVES_MAX_WAVELENGTHS
                             if sim.arch == Arch.PROWAVES else
                             sim.wavelengths, jnp.int32),
        prev_active=_activity_mask(
            jnp.full((cfg.n_chiplets,), cfg.max_gateways_per_chiplet,
                     jnp.int32), sim))


def _scan_trace(state: SimState, xs, sim: SimConfig, tables: Optional[dict],
                topo: Optional[dict], faulted: bool = False,
                dest: Optional[jax.Array] = None) -> Tuple[SimState, dict]:
    """Run the per-interval scan; the ONE place the trace counter bumps.

    With `sim.epoch_kernel` set the whole interval scan runs as the fused
    `kernels.epoch_step` Pallas kernel (one kernel launch for T intervals)
    on the configurations it supports: RESIPI/RESIPI_ALL on an unpadded
    topology. PROWAVES/AWGR and the padded-topology paths, and every parity
    oracle, take the lax.scan body below. Both bodies share the trace
    counter (one trace per scan, whichever engine executes it); kernel
    traces are also counted on their own (`engine_stats()["kernel_traces"]`).
    """
    _STATS["traces"] += 1
    if sim.epoch_kernel and topo is None \
            and sim.arch in (Arch.RESIPI, Arch.RESIPI_ALL):
        from repro.kernels.epoch_step.ops import epoch_run_pallas
        _STATS["kernel_traces"] += 1
        return epoch_run_pallas(state, xs, sim, tables,
                                dest=dest, faulted=faulted)
    step = make_step(sim, tables, topo, faulted=faulted, dest=dest)
    return jax.lax.scan(step, state, xs)


def _record_sums(recs: dict, t_mask: jax.Array) -> dict:
    """Mask-correct record totals: the sufficient statistics every summary
    (one-shot, padded lane, or streaming accumulation) is computed from.
    Records are already t_valid-masked in the scan body, so plain sums
    ignore padded tail intervals by construction."""
    return {
        "latency": jnp.sum(recs["latency"]),
        "power_mw": jnp.sum(recs["power_mw"]),
        "energy": jnp.sum(recs["energy"]),
        "gateways": jnp.sum(recs["g"]).astype(jnp.float32),
        "wavelengths": jnp.sum(recs["wavelengths"]),
        "saturated": jnp.sum(recs["saturated"].astype(jnp.float32)),
        "reconfig_nj": jnp.sum(recs["reconfig_nj"]),
        "valid_intervals": jnp.sum(t_mask),
    }


def _summary_from_sums(sums: dict, n_chiplets_for_lambda) -> dict:
    """Summary means from `_record_sums` totals.

    `n_chiplets_for_lambda` is the per-interval lambda-record width used to
    normalize mean_wavelengths (the real chiplet count on padded paths).
    """
    t = jnp.maximum(sums["valid_intervals"], 1.0)
    return {
        "mean_latency": sums["latency"] / t,
        "mean_power_mw": sums["power_mw"] / t,
        "mean_energy": sums["energy"] / t,
        "mean_gateways": sums["gateways"] / t,
        "mean_wavelengths": sums["wavelengths"]
                            / (t * n_chiplets_for_lambda),
        "saturated_frac": sums["saturated"] / t,
        "total_reconfig_nj": sums["reconfig_nj"],
        "valid_intervals": sums["valid_intervals"],
    }


# The summary schema `_summary_from_sums` emits, as a fixed-order tuple:
# the device-resident search (repro.core.search) packs best-candidate
# summaries as vectors in this order, and both search engines validate
# objectives against it — keep in sync with the dict above (pinned by
# tests/test_search.py).
SUMMARY_KEYS = ("mean_latency", "mean_power_mw", "mean_energy",
                "mean_gateways", "mean_wavelengths", "saturated_frac",
                "total_reconfig_nj", "valid_intervals")

# Short objective names accepted by the placement search engines.
PLACEMENT_OBJECTIVE_ALIASES = {"latency": "mean_latency",
                               "power": "mean_power_mw",
                               "energy": "mean_energy"}


def check_placement_objective(objective: str) -> None:
    """Shared search-objective validation (host and device engines)."""
    if objective == "inter_latency":
        return
    if PLACEMENT_OBJECTIVE_ALIASES.get(objective, objective) \
            not in SUMMARY_KEYS:
        raise ValueError(
            f"unknown placement objective {objective!r} (use "
            f"'inter_latency', 'latency', 'power', 'energy' or a summary "
            f"key: {sorted(SUMMARY_KEYS)})")


def _masked_xs(ext: jax.Array, mem: jax.Array, intra: jax.Array,
               ext_frac: jax.Array, t_mask: jax.Array,
               faults: Optional[Tuple[jax.Array, ...]] = None) -> tuple:
    """The per-interval scan inputs of one lane: (ext, mem, intra, ext_frac
    broadcast over T, float t_mask), every load of a masked interval zeroed
    so it injects nothing, then the fault frame when one rides."""
    t_mask = t_mask.astype(jnp.float32)
    xs = (ext * t_mask[:, None], mem * t_mask, intra * t_mask[:, None],
          jnp.broadcast_to(ext_frac, mem.shape), t_mask)
    return xs if faults is None else xs + tuple(faults)


def _simulate_impl(ext: jax.Array, mem: jax.Array, intra: jax.Array,
                   ext_frac: jax.Array, t_mask: jax.Array, sim: SimConfig,
                   tables: dict, ov: Optional[Dict[str, jax.Array]] = None,
                   topo: Optional[dict] = None,
                   faults: Optional[Tuple[jax.Array, ...]] = None,
                   dest: Optional[jax.Array] = None) -> dict:
    """Scan body shared by every entry point (single / batch / sweep).

    With `topo` the trace/state is padded on the chiplet axis: `sim.cfg`
    describes the *padded* shape (grid maxima) and `topo` carries the
    per-topology actuals. Padded chiplets start with g=0 and lambda=0,
    inject zero traffic, and — because the controller thresholds can only
    raise g on positive load — stay dark for the whole scan.

    `t_mask` [T] is the time-axis validity vector (all-ones for full-length
    traces): masked intervals inject zero traffic, record zeros everywhere,
    and are excluded from every summary mean, so a tail-padded trace is
    bit-equivalent to its unpadded original.
    """
    sim = _apply_overrides(sim, ov)
    cfg = sim.cfg
    xs = _masked_xs(ext, mem, intra, ext_frac, t_mask, faults)
    if topo is None:
        state0 = _initial_state(sim)
    else:
        valid = jnp.arange(cfg.n_chiplets) < topo["n_chiplets"]
        chip_mask = valid.astype(jnp.float32)
        topo = dict(topo, chip_mask=chip_mask)
        xs = (xs[0] * chip_mask, xs[1], xs[2] * chip_mask) + xs[3:]
        if dest is not None:
            # Padded chiplet columns receive nothing and padded rows send
            # nothing; surviving rows re-normalize to row-stochastic with
            # the same formula as traffic.slice_trace, so the padded view
            # prices destinations exactly like the sliced one.
            d = dest * chip_mask[None, :] * chip_mask[:, None]
            row = jnp.sum(d, axis=-1, keepdims=True)
            dest = jnp.where(row > 0.0, d / jnp.maximum(row, 1e-12), 0.0)
        g0 = jnp.where(valid,
                       jnp.asarray(sim.ctl.max_gateways).astype(jnp.int32),
                       0)
        w0 = PROWAVES_MAX_WAVELENGTHS if sim.arch == Arch.PROWAVES \
            else sim.wavelengths
        state0 = SimState(
            ctl=ControllerState(
                g=g0,
                packets_seen=jnp.zeros((cfg.n_chiplets,), jnp.float32),
                epoch=jnp.int32(0)),
            wavelengths=jnp.where(valid,
                                  jnp.asarray(w0).astype(jnp.int32), 0),
            prev_active=jnp.zeros((cfg.total_gateways,), bool))

    _, recs = _scan_trace(state0, xs, sim, tables, topo,
                          faulted=faults is not None, dest=dest)

    # Masked chiplet lanes record lambda=0 and must not dilute the
    # per-chiplet average on padded-topology paths.
    n_lam = cfg.n_chiplets if topo is None \
        else jnp.maximum(jnp.sum(topo["chip_mask"]), 1.0)
    summary = _summary_from_sums(_record_sums(recs, xs[4]), n_lam)
    return {"records": recs, "summary": summary}


def _chunk_impl(state: SimState, ext, mem, intra, ext_frac, t_mask,
                tables: dict, faults, dest, sim: SimConfig):
    """One session chunk of one lane: scan from the carried `state`; returns
    (new state, records, `_record_sums` totals). An all-masked lane injects
    nothing, records zeros and freezes its carry."""
    xs = _masked_xs(ext, mem, intra, ext_frac, t_mask, faults)
    new_state, recs = _scan_trace(state, xs, sim, tables, None,
                                  faulted=faults is not None, dest=dest)
    return new_state, recs, _record_sums(recs, xs[4])


def _trace_arrays(trace: dict) -> Tuple[jax.Array, ...]:
    """(ext, mem, intra, ext_frac, t_mask, dest) — dest is None (an empty
    jit/vmap pytree, so destination-free traces keep their exact executable
    signatures) unless the trace carries a destination matrix. Each array
    keeps its kind (`transform._as_array`): a host batch (`stack_traces`)
    reaches the jitted entry as its arguments, with no eager device op."""
    traffic.validate_trace(trace)
    mem = trace["mem_load"]
    t_mask = trace.get("t_mask")
    t_mask = _xp(mem).ones(jnp.shape(mem), np.float32) if t_mask is None \
        else _as_array(t_mask, np.float32)
    dest = trace.get("dest")
    dest = None if dest is None else _as_array(dest, np.float32)
    return (trace["ext_load"], mem, trace["int_load"],
            _as_array(trace["ext_frac"]), t_mask, dest)


def _fault_xs(frame: dict, n_intervals: int,
              what: str) -> Tuple[jax.Array, ...]:
    """A fault frame (`faults.compile_faults`, or K of them stacked) as
    scan xs: (gw_ok [..., T, C, G], stuck_on [..., T, C, G], drift_db
    [..., T]), float32, each of the frame's array kind. Raises when a key
    is missing or T is not `n_intervals`, the length of `what`."""
    missing = [k for k in FAULT_KEYS if k not in frame]
    if missing:
        present = [k for k in FAULT_KEYS if k in frame]
        raise ValueError(
            f"the fault frame of {what} has {present} but is missing "
            f"{missing} — build a complete one with faults.compile_faults "
            f"or faults.no_faults (faults.attach_faults on a trace)")
    flt = tuple(_as_array(frame[k], np.float32) for k in FAULT_KEYS)
    t = int(flt[0].shape[-3])
    if t != n_intervals:
        raise ValueError(
            f"fault frames cover {t} intervals but {what} has "
            f"{n_intervals} — compile them with n_intervals={n_intervals}")
    return flt


def _trace_faults(trace: dict) -> Optional[Tuple[jax.Array, ...]]:
    """The trace's fault frame as scan xs (`_fault_xs`), or None when it
    carries none. A partial frame raises."""
    if not any(k in trace for k in FAULT_KEYS):
        return None
    return _fault_xs(trace, int(jnp.shape(trace["mem_load"])[-1]),
                     "the trace")


# One jitted program per entry point. `dest` and `faults` are trailing
# optional pytrees: None adds no operand, so a clean call keeps its program;
# present, each gets its own compiled program in the same jit cache.

@functools.partial(jax.jit, static_argnames=("sim",))
def _simulate_jit(ext, mem, intra, ext_frac, t_mask, tables, dest=None,
                  faults=None, *, sim: SimConfig):
    return _simulate_impl(ext, mem, intra, ext_frac, t_mask, sim, tables,
                          faults=faults, dest=dest)


@functools.partial(jax.jit, static_argnames=("sim",))
def _sweep_jit(ext, mem, intra, ext_frac, t_mask, tables, ov, dest=None,
               faults=None, *, sim: SimConfig):
    """K runtime-override points over one trace, zipped with K stacked
    fault frames when `faults` is given (`sweep_faults`); with `ov={}` the
    axis size comes from the frames alone."""
    return jax.vmap(
        lambda fl, o: _simulate_impl(ext, mem, intra, ext_frac, t_mask, sim,
                                     tables, o, faults=fl, dest=dest)
    )(faults, ov)


@functools.partial(jax.jit, static_argnames=("sim",))
def _sweep_batch_jit(ext, mem, intra, ext_frac, t_mask, tables, ov,
                     dest=None, *, sim: SimConfig):
    def one_trace(e, m, i, f, t, d):
        return jax.vmap(
            lambda o: _simulate_impl(e, m, i, f, t, sim, tables, o,
                                     dest=d))(ov)
    return jax.vmap(one_trace)(ext, mem, intra, ext_frac, t_mask, dest)


@functools.partial(jax.jit, static_argnames=("sim",))
def _sweep_topology_jit(ext, mem, intra, ext_frac, t_mask, topo, ov,
                        dest=None, *, sim: SimConfig):
    # `dest` is the one generated-at-c_max matrix, closed over the K-point
    # vmap: each point masks/re-normalizes it to its own chiplet count
    # inside `_simulate_impl` (traced chip_mask), so one matrix serves the
    # whole padded grid.
    return jax.vmap(
        lambda tp, o: _simulate_impl(ext, mem, intra, ext_frac, t_mask,
                                     sim, None, o, topo=tp,
                                     dest=dest))(topo, ov)


@functools.partial(jax.jit, static_argnames=("sim",))
def _sweep_topology_batch_jit(ext, mem, intra, ext_frac, t_mask, topo, ov,
                              dest=None, *, sim: SimConfig):
    def one_trace(e, m, i, f, t, d):
        return jax.vmap(
            lambda tp, o: _simulate_impl(e, m, i, f, t, sim, None,
                                         o, topo=tp, dest=d))(topo, ov)
    return jax.vmap(one_trace)(ext, mem, intra, ext_frac, t_mask, dest)


@functools.partial(jax.jit, static_argnames=("sim",))
def _sweep_workload_jit(ext, mem, intra, ext_frac, t_mask, tables, ov,
                        dest=None, faults=None, *, sim: SimConfig):
    """K workload lanes zipped with K runtime-override lanes (one scan);
    `simulate_batch` runs it with `ov=None` and per-lane fault frames."""
    return jax.vmap(
        lambda e, m, i, f, t, o, d, fl: _simulate_impl(
            e, m, i, f, t, sim, tables, o, faults=fl, dest=d)
    )(ext, mem, intra, ext_frac, t_mask, ov, dest, faults)


@functools.partial(jax.jit, static_argnames=("sim",))
def _sweep_workload_topo_jit(ext, mem, intra, ext_frac, t_mask, topo, ov,
                             dest=None, *, sim: SimConfig):
    """K workload lanes zipped with K padded-topology/placement lanes."""
    return jax.vmap(
        lambda e, m, i, f, t, tp, o, d: _simulate_impl(e, m, i, f, t, sim,
                                                       None, o, topo=tp,
                                                       dest=d)
    )(ext, mem, intra, ext_frac, t_mask, topo, ov, dest)


@functools.partial(jax.jit, static_argnames=("sim",), donate_argnums=(0,))
def _session_chunk_jit(state, ext, mem, intra, ext_frac, t_mask, tables,
                       dest=None, faults=None, *, sim: SimConfig):
    """One streaming chunk: `_chunk_impl` with the carry donated (the old
    state's buffers are reused in place). The chunk's fault-frame slice
    (aligned by chunk_trace, which slices FAULT_KEYS with the loads) rides
    as `faults`."""
    return _chunk_impl(state, ext, mem, intra, ext_frac, t_mask, tables,
                       faults, dest, sim)


@functools.partial(jax.jit, static_argnames=("sim",))
def _session_tick_jit(states, ext, mem, intra, ext_frac, t_mask, tables,
                      dest=None, faults=None, *, sim: SimConfig):
    """One continuous-batching server tick: B session carries advance
    through B masked chunk scans as ONE vmapped executable.

    Lane semantics are exactly `_session_chunk_jit` per lane (the vmap is
    bit-transparent on CPU — pinned by tests/test_serve.py): a lane whose
    `t_mask` row is all zeros injects nothing, records zeros, and FREEZES
    its carry, so empty / backing-off / parked lanes ride along for free
    and the executable's [B, T] shape never changes across ticks. `dest`
    is per lane; the fault frame lives on hardware time and is SHARED by
    every lane (closed over, not vmapped).
    """
    return jax.vmap(
        lambda st, e, m, i, f, t, d: _chunk_impl(st, e, m, i, f, t, tables,
                                                 faults, d, sim)
    )(states, ext, mem, intra, ext_frac, t_mask, dest)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def simulate(trace: dict, sim: SimConfig) -> dict:
    """Run a full trace; returns per-interval records + summary scalars.

    Compile-once: `sim` is a static jit argument, so a second call with an
    equal config and trace shape re-traces nothing (engine_stats() shows the
    counter), and the selection tables are memoized per NetworkConfig.

    A fault frame the trace carries (faults.attach_faults) reaches the scan
    as an optional `faults` pytree: its own compiled program when present,
    so traces without one never pay for the fault arithmetic.
    """
    return _simulate_jit(*_simulate_operands(trace, sim), sim=sim)


def _simulate_operands(trace: dict, sim: SimConfig) -> tuple:
    """The operands `simulate` hands `_simulate_jit` (`simulate_batch`
    builds them for a stacked batch; runtime.cache lowers the same)."""
    ext, mem, intra, ext_frac, t_mask, dest = _trace_arrays(trace)
    return (ext, mem, intra, ext_frac, t_mask, selection_tables_jax(sim.cfg),
            dest, _trace_faults(trace))


def simulate_eager(trace: dict, sim: SimConfig) -> dict:
    """Seed-parity path: rebuild tables and re-trace the scan every call.

    Kept as the benchmark baseline (bench_engine.py) — do not use in sweeps.
    """
    tables = rebuild_selection_tables(sim.cfg)
    ext, mem, intra, ext_frac, t_mask, dest = _trace_arrays(trace)
    return _simulate_impl(ext, mem, intra, ext_frac, t_mask, sim, tables,
                          dest=dest)


def rebuild_selection_tables(cfg: NetworkConfig) -> dict:
    """Uncached table build (bypasses both lru_caches) for baselines."""
    return build_selection_tables.__wrapped__(cfg).as_jax()


# Deprecated pre-PEP8 alias (PR 3 rename): kept so bench_engine.py baselines
# recorded against the old name keep importing/running unchanged.
SelectionTables_rebuild = rebuild_selection_tables


def _stack_host(parts: list) -> np.ndarray:
    """`jnp.stack` of host values, on the host: each part canonicalized
    as `jnp.asarray` would, then cast to the dtype `jnp.stack` promotes
    them to."""
    parts = [_as_array(np.asarray(p)) for p in parts]
    dtype = jnp.result_type(*parts)
    return np.stack([p.astype(dtype, copy=False) for p in parts])


@spans.root("sim.stack_traces")
def stack_traces(traces: List[dict], *, pad: bool = False) -> dict:
    """Stack N traces along a new leading batch axis.

    Same-length traces stack directly (the pre-PR-4 behavior). Mixed-length
    (ragged-T) traces need `pad=True`: shorter traces zero-pad to the
    longest T and the stacked dict carries a `t_mask` [N, T] validity mask
    — masked tail intervals contribute exactly zero to every engine
    reduction, so padded lane k simulates identically to the unpadded
    trace k. Without `pad=True`, ragged inputs raise a ValueError naming
    the lengths (instead of the old cryptic jnp stacking error).

    Concrete traces (no leaf a tracer) stack on the host: one
    `jax.device_get` brings every trace across (span `sim.fetch_traces`),
    validation and padding read those copies, and the batch comes out as
    numpy arrays, in the dtypes `jnp.stack` would give. Under `jit` or
    `vmap` the traces stack through `jax.numpy`.
    """
    if not traces:
        raise ValueError("stack_traces() needs at least one trace")
    host = not any(isinstance(x, jax.core.Tracer)
                   for x in jax.tree_util.tree_leaves(traces))
    if host:
        with spans.span("sim.fetch_traces"):
            traces = jax.device_get(list(traces))
    for i, tr in enumerate(traces):
        traffic.validate_trace(tr, who=f"traces[{i}]")
    chips = sorted({int(jnp.shape(tr["ext_load"])[-1]) for tr in traces})
    if len(chips) != 1:
        raise ValueError(
            f"traces cover different chiplet counts {chips}; narrow them "
            f"to one width first (traffic.slice_trace)")
    lengths = [int(jnp.shape(tr["ext_load"])[0]) for tr in traces]
    ragged = len(set(lengths)) > 1
    if ragged and not pad:
        raise ValueError(
            f"traces have mixed lengths T={lengths}; pass pad=True to "
            f"zero-pad them to T={max(lengths)} under a t_mask (the "
            f"ragged/padded batch path — simulate_batch/sweep_batch/"
            f"sweep_workload do this automatically for list inputs)")
    masked = pad or ragged or any("t_mask" in tr for tr in traces)
    if masked:
        traces = [traffic.pad_trace(tr, max(lengths)) for tr in traces]
    n_faulted = sum(_trace_faults(tr) is not None for tr in traces)
    if n_faulted not in (0, len(traces)):
        raise ValueError(
            f"{n_faulted}/{len(traces)} traces carry fault frames; a "
            f"batch must be uniformly faulted or uniformly clean (attach "
            f"faults.no_faults frames to the clean ones)")
    n_dest = sum(tr.get("dest") is not None for tr in traces)
    if n_dest not in (0, len(traces)):
        raise ValueError(
            f"{n_dest}/{len(traces)} traces carry destination matrices; a "
            f"batch must be uniformly destination-aware or uniformly "
            f"uniform-destination (generate every trace with dest=True, "
            f"or none)")
    keys = ("ext_load", "mem_load", "int_load", "ext_frac") \
        + (("t_mask",) if masked else ()) \
        + (("dest",) if n_dest else ()) \
        + (FAULT_KEYS if n_faulted else ())
    stack = _stack_host if host \
        else (lambda parts: jnp.stack([jnp.asarray(p) for p in parts]))
    out = {k: stack([tr[k] for tr in traces]) for k in keys}
    out["app"] = [tr.get("app", "?") for tr in traces]
    return out


def simulate_batch(traces, sim: SimConfig) -> dict:
    """Batched simulate: one vmapped, jit-cached scan over N traces.

    `traces` is either a list of trace dicts (stacked here; mixed-length
    traces pad to the longest T under a `t_mask`) or an already-stacked
    dict with a leading batch axis (from `stack_traces`). Records and
    summary values gain that leading [N] axis; for ragged batches the
    records of shorter lanes are zero beyond their own T.
    """
    batch = stack_traces(traces, pad=True) \
        if isinstance(traces, (list, tuple)) else traces
    ext, mem, intra, ext_frac, t_mask, tables, dest, flt = \
        _simulate_operands(batch, sim)
    return _sweep_workload_jit(ext, mem, intra, ext_frac, t_mask, tables,
                               None, dest, flt, sim=sim)


def sweep(trace: dict, sim: SimConfig, **fields) -> dict:
    """Vmapped DSE over scalar config fields, e.g.::

        sweep(tr, sim, l_m=jnp.linspace(0.005, 0.03, 64))

    Every swept field (see SWEEPABLE_FIELDS) gets a 1-D array of values; all
    arrays must share one length K. The K simulations run as a single
    compiled vmapped scan — results carry a leading [K] axis. Compilation is
    cached on (trace shape, config, set of swept fields, grid length K),
    not on the grid *values*, so re-sweeping a same-sized grid elsewhere in
    the space is compile-free.
    """
    return _sweep_jit(*_sweep_operands(trace, sim, fields), sim=sim)


def _sweep_operands(trace: dict, sim: SimConfig, fields: dict) -> tuple:
    """The operands `sweep` hands `_sweep_jit` (and `sweep_batch`
    `_sweep_batch_jit`; runtime.cache lowers the same)."""
    ov = _check_sweep_fields(fields)
    ext, mem, intra, ext_frac, t_mask, dest = _trace_arrays(trace)
    return (ext, mem, intra, ext_frac, t_mask, selection_tables_jax(sim.cfg),
            ov, dest)


def _check_sweep_fields(fields) -> Dict[str, jax.Array]:
    if not fields:
        raise ValueError("sweep() needs at least one field=values pair")
    ov = {k: jnp.asarray(v) for k, v in fields.items()}
    lengths = {k: a.shape for k, a in ov.items()}
    if any(len(s) != 1 for s in lengths.values()) \
            or len({s[0] for s in lengths.values()}) != 1:
        raise ValueError(f"swept fields must be 1-D of equal length, "
                         f"got {lengths}")
    return ov


@spans.root("sim.sweep_batch")
def sweep_batch(traces, sim: SimConfig, **fields) -> dict:
    """Full DSE grid in ONE compiled call: N traces x K parameter values.

    Combines `simulate_batch` and `sweep`: results carry leading [N, K]
    axes (trace-major). fig10's app x gateway-count exploration is a single
    call of this with `max_gateways`/`min_gateways` pinned per grid point.
    """
    batch = stack_traces(traces, pad=True) \
        if isinstance(traces, (list, tuple)) else traces
    args = _sweep_operands(batch, sim, fields)
    with spans.span("sim.dispatch"):
        return _sweep_batch_jit(*args, sim=sim)


def sweep_faults(trace: dict, sim: SimConfig, frames, **fields) -> dict:
    """K fault scenarios over one trace in a single compiled vmapped scan.

    `frames` is a list of fault frames (each from `faults.compile_faults`
    on the same horizon as the trace) or an already-stacked frame dict with
    a leading [K] axis (`faults.stack_fault_frames`). Optional `**fields`
    grids (SWEEPABLE_FIELDS, each length K) zip lane-for-lane with the
    fault axis, so fault scenarios compose with every runtime-override
    sweep axis. Results carry a leading [K] axis; compilation caches on
    (trace shape, config, K, swept-field set), not on which faults fire.
    The frames are `_sweep_jit`'s optional `faults` pytree: its own
    compiled program when present.
    """
    if _trace_faults(trace) is not None:
        raise ValueError(
            "sweep_faults() takes the fault grid via `frames`; pass a clean "
            "trace (faults.strip_faults) instead of an attached one")
    stacked = stack_fault_frames(frames) \
        if isinstance(frames, (list, tuple)) else frames
    ext, mem, intra, ext_frac, t_mask, dest = _trace_arrays(trace)
    flt = _fault_xs(stacked, int(jnp.shape(mem)[0]), "the trace")
    k = int(flt[0].shape[0])
    # Without fields the override pytree is empty: it has no mapped leaves,
    # so the vmap axis size comes from the fault frames alone.
    ov = _check_sweep_fields(fields) if fields else {}
    k_ov = next(iter(ov.values())).shape[0] if ov else k
    if k_ov != k:
        raise ValueError(
            f"swept fields have length {k_ov} but there are {k} fault "
            f"frames — the axes zip lane-for-lane")
    return _sweep_jit(ext, mem, intra, ext_frac, t_mask,
                      selection_tables_jax(sim.cfg), ov, dest, flt, sim=sim)


# ---------------------------------------------------------------------------
# Topology-polymorphic padded sweeps
# ---------------------------------------------------------------------------

def topology_point_config(sim: SimConfig, *, n_chiplets: int = None,
                          gateways_per_chiplet: int = None,
                          mesh_radix: int = None,
                          gateway_positions=None) -> SimConfig:
    """Unpadded SimConfig equivalent to one `sweep_topology` grid point.

    The controller's gateway bounds are clamped to the topology's per-chiplet
    gateway count, matching the padded engine's semantics. Used by parity
    tests and the compile-farm benchmark baseline. `gateway_positions` pins
    the point's placement (None keeps the base config's placement, which a
    `mesh_radix` change resets to the default edge scheme).
    """
    cfg = sim.cfg.with_topology(n_chiplets=n_chiplets,
                                gateways_per_chiplet=gateways_per_chiplet,
                                mesh_radix=mesh_radix)
    if gateway_positions is not None:
        cfg = cfg.with_placement(normalize_placement(gateway_positions))
    g = cfg.max_gateways_per_chiplet
    ctl = dataclasses.replace(
        sim.ctl, max_gateways=min(sim.ctl.max_gateways, g),
        min_gateways=min(sim.ctl.min_gateways, g))
    return dataclasses.replace(sim, cfg=cfg, ctl=ctl)


def _prepare_topology_sweep(sim: SimConfig, grids: dict):
    """Split grids into topology axes + runtime overrides; build the padded
    static config, per-topology traced arrays, and controller clamps.

    Returns (sim_padded, topo, ov, c_max) where `sim_padded.cfg` describes
    the PADDED shapes (grid maxima — the one compiled executable's shape)
    and `topo` holds the per-grid-point actual topology as traced arrays.
    """
    if not grids:
        raise ValueError("sweep_topology() needs at least one field=values "
                         f"pair from {TOPOLOGY_SWEEPABLE_FIELDS}")
    lengths = {k: _grid_len(k, v) for k, v in grids.items()}
    topo_grids = {k: list(v) for k, v in grids.items()
                  if k in TOPOLOGY_SWEEPABLE_FIELDS}
    other = {k: v for k, v in grids.items()
             if k not in TOPOLOGY_SWEEPABLE_FIELDS}
    unknown = set(other) - set(SWEEPABLE_FIELDS)
    if unknown:
        raise ValueError(
            f"non-sweepable fields: {sorted(unknown)} (topology: "
            f"{TOPOLOGY_SWEEPABLE_FIELDS}, runtime: {SWEEPABLE_FIELDS})")
    if not topo_grids:
        raise ValueError("no topology fields swept — use sweep() for "
                         "runtime-only grids")
    if len(set(lengths.values())) != 1:
        raise ValueError(f"swept fields must share one length, "
                         f"got {lengths}")
    k = next(iter(lengths.values()))

    cfg = sim.cfg
    cs = [int(x) for x in topo_grids.get("n_chiplets",
                                         [cfg.n_chiplets] * k)]
    gs = [int(x) for x in topo_grids.get(
        "gateways_per_chiplet", [cfg.max_gateways_per_chiplet] * k)]
    rs = [int(x) for x in topo_grids.get("mesh_radix", [cfg.mesh_x] * k)]
    if "gateway_positions" in topo_grids:
        ps = [normalize_placement(p)
              for p in topo_grids["gateway_positions"]]
    else:
        # with_topology's contract: a mesh_radix change invalidates the
        # base config's explicit placement (its coordinates belong to the
        # old mesh), so such grid points fall back to the default edge
        # scheme — matching topology_point_config and keeping the
        # padded==unpadded parity invariant.
        ps = [normalize_placement(cfg.gateway_positions)
              if r == cfg.mesh_x and r == cfg.mesh_y else None
              for r in rs]
    if min(cs) < 1 or min(gs) < 1 or min(rs) < 2:
        raise ValueError(f"invalid topology grid: n_chiplets {cs}, "
                         f"gateways {gs}, radix {rs}")
    for i, (g, p) in enumerate(zip(gs, ps)):
        avail = N_DEFAULT_EDGE_SLOTS if p is None else len(p)
        if g > avail:
            raise ValueError(
                f"grid point {i}: gateways_per_chiplet={g} exceeds the "
                f"{avail} placed gateway positions "
                f"({'default edge scheme' if p is None else p})")

    cfgs = tuple(dataclasses.replace(
        cfg.with_topology(n_chiplets=c, gateways_per_chiplet=g,
                          mesh_radix=r), gateway_positions=p)
                 for c, g, r, p in zip(cs, gs, rs, ps))
    c_max, g_max, r_max = max(cs), max(gs), max(rs)
    ptab = padded_selection_tables_jax(cfgs, (g_max, r_max * r_max))
    topo = {
        "n_chiplets": jnp.asarray(cs, jnp.int32),
        "g_max": jnp.asarray(gs, jnp.int32),
        "src_hops": ptab["src_hops"],                       # [K, g_max]
        "gw_loss_db": ptab["gw_loss_db"],                   # [K, g_max]
        "mesh_hops": jnp.asarray(
            [uniform_mesh_mean_hops(c) for c in cfgs], jnp.float32),
        "mesh_x": jnp.asarray(rs, jnp.float32),
        "total_gateways": jnp.asarray(
            [c.total_gateways for c in cfgs], jnp.float32),
    }

    # Controller gateway bounds ride the existing runtime-override path,
    # clamped per grid point to the topology's gateway count.
    ov = {f: jnp.asarray(v) for f, v in other.items()}
    user_max = ov.pop("max_gateways", jnp.int32(sim.ctl.max_gateways))
    user_min = ov.pop("min_gateways", jnp.int32(sim.ctl.min_gateways))
    maxg = jnp.minimum(jnp.broadcast_to(jnp.asarray(user_max, jnp.int32),
                                        (k,)), topo["g_max"])
    ming = jnp.minimum(jnp.broadcast_to(jnp.asarray(user_min, jnp.int32),
                                        (k,)), maxg)
    ov["max_gateways"] = maxg
    ov["min_gateways"] = ming

    sim_padded = dataclasses.replace(sim, cfg=dataclasses.replace(
        cfg, n_chiplets=c_max, max_gateways_per_chiplet=g_max,
        mesh_x=r_max, mesh_y=r_max))
    return sim_padded, topo, ov, c_max


def _topo_trace_arrays(trace_or_batch, c_max: int):
    if _trace_faults(trace_or_batch) is not None:
        raise ValueError(
            "fault frames are not supported on the padded-topology paths "
            "(sweep_topology / shard_sweep): fault frames are compiled "
            "against ONE topology's [C, G] slot grid and cannot be "
            "re-padded per grid point. strip_faults(trace) first, or use "
            "simulate / sweep_faults on a fixed topology.")
    ext, mem, intra, ext_frac, t_mask, dest = _trace_arrays(trace_or_batch)
    if ext.shape[-1] < c_max:
        raise ValueError(
            f"trace covers {ext.shape[-1]} chiplets but the grid needs "
            f"{c_max}; generate it with cfg.with_topology(n_chiplets="
            f"{c_max}) (see traffic.generate_trace)")
    if dest is not None:
        # Narrow to the padded chiplet axis; per-grid-point masking and row
        # re-normalization happen inside _simulate_impl against chip_mask.
        from repro.core.traffic.transform import _renormalize_rows
        dest = _renormalize_rows(dest[..., :c_max, :c_max])
    return ext[..., :c_max], mem, intra[..., :c_max], ext_frac, t_mask, dest


def sweep_topology(trace: dict, sim: SimConfig, **grids) -> dict:
    """Topology DSE over shape-changing axes in ONE compiled executable.

    ::

        sweep_topology(tr, sim, n_chiplets=[4, 16, 64],
                       gateways_per_chiplet=[4, 4, 2])

    Every topology field (TOPOLOGY_SWEEPABLE_FIELDS) gets a 1-D grid; all
    grids (topology + any runtime SWEEPABLE_FIELDS) share one length K and
    are zipped into K grid points. Instead of compiling one executable per
    topology shape, every per-topology array is padded to the grid maxima
    with a validity mask, and the K masked scans run as a single vmapped,
    jit-cached call (engine_stats() shows one scan-body trace per grid
    *shape*, not per topology).

    Masking invariant: padded chiplet/gateway slots hold zero load, g=0 and
    lambda=0 for the whole scan, so they contribute exactly zero to every
    latency/power/energy reduction — `sweep_topology` at pad==actual size
    matches unpadded `simulate` to float tolerance (tested).

    The trace must cover max(n_chiplets) chiplets; each grid point uses its
    first n_chiplets columns (traffic.slice_trace view). Results carry a
    leading [K] axis; per-chiplet records are padded to the grid maximum.
    Controller gateway bounds are clamped per point to the topology's
    gateway count (see `topology_point_config`).
    """
    args, sim_p = _sweep_topology_operands(trace, sim, grids)
    return _sweep_topology_jit(*args, sim=sim_p)


def _sweep_topology_operands(trace: dict, sim: SimConfig,
                             grids: dict) -> Tuple[tuple, SimConfig]:
    """(operands, padded static config) of the padded topology jits, for
    one trace or a stacked batch (runtime.cache lowers the same)."""
    sim_p, topo, ov, c_max = _prepare_topology_sweep(sim, grids)
    ext, mem, intra, ext_frac, t_mask, dest = _topo_trace_arrays(trace, c_max)
    return (ext, mem, intra, ext_frac, t_mask, topo, ov, dest), sim_p


@spans.root("sim.sweep_topology_batch")
def sweep_topology_batch(traces, sim: SimConfig, *, devices=None,
                         **grids) -> dict:
    """N traces x K topologies in ONE compiled call ([N, K] results).

    The topology analogue of `sweep_batch`: `traces` is a list of same-shape
    trace dicts or an already-stacked dict from `stack_traces`. Pass
    `devices` (more than one — e.g. the fleet's global device list) to
    shard the K axis via `shard_sweep`.
    """
    if devices is not None and len(list(devices)) > 1:
        return shard_sweep(traces, sim, devices=devices, **grids)
    batch = stack_traces(traces, pad=True) \
        if isinstance(traces, (list, tuple)) else traces
    args, sim_p = _sweep_topology_operands(batch, sim, grids)
    with spans.span("sim.dispatch"):
        return _sweep_topology_batch_jit(*args, sim=sim_p)


def _sharding_note(out: dict, describe: dict) -> dict:
    """Attach sharding metadata to a sweep result (no silent pads): the
    pad-lane count lands in the returned summary and the full placement
    description under a top-level "sharding" key."""
    out = dict(out)
    if "summary" in out and isinstance(out["summary"], dict):
        out["summary"] = dict(out["summary"],
                              pad_lanes=int(describe["pad_lanes"]))
    out["sharding"] = dict(describe)
    return out


def shard_sweep(traces, sim: SimConfig, *, devices=None, **grids) -> dict:
    """Multi-device / multi-host topology sweep: the [N x K] grid sharded.

    The K (topology) axis of the padded grid is placed with a 1-D
    `NamedSharding` over the fleet's "grid" mesh axis, so the SAME compiled
    executable partitions the vmapped scans across all available devices
    (GSPMD); N-trace batches replicate the trace and shard the topology
    axis. After `repro.core.distributed.init_distributed` the default
    device list spans every fleet process and the same placement shards
    across hosts (trace arrays are then replicated fleet-wide and results
    all-gathered, so every process returns the full grid). K is padded to
    a multiple of the device count by repeating the last grid point —
    logged, sliced off the results, and reported as `summary["pad_lanes"]`
    plus a top-level `"sharding"` dict (no silent caps). With one device
    it runs the single-device `sweep_topology` path; a sharded run that
    fails raises rather than quietly rerunning on one device.

    Accepts a single trace dict or a list/stacked batch (leading [N] axis
    in the results, as `sweep_topology_batch`).
    """
    from repro.core.distributed import GridSharding

    batched = not (isinstance(traces, dict)
                   and jnp.ndim(traces["ext_load"]) == 2)
    single_call = sweep_topology_batch if batched else sweep_topology
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) <= 1:
        out = single_call(traces, sim, **grids)
        return _sharding_note(out, {
            "grid_points": int(np.asarray(
                out["summary"]["mean_latency"]).shape[-1]),
            "pad_lanes": 0, "devices": 1, "processes": 1})

    batch = stack_traces(traces, pad=True) \
        if isinstance(traces, (list, tuple)) else traces
    (ext, mem, intra, ext_frac, t_mask, topo, ov, dest), sim_p = \
        _sweep_topology_operands(batch, sim, grids)

    k = int(topo["n_chiplets"].shape[0])
    gs = GridSharding(k, devices=devices)
    topo = gs.shard(topo)
    ov = gs.shard(ov)
    ext, mem, intra, ext_frac, t_mask, dest = gs.replicate(
        (ext, mem, intra, ext_frac, t_mask, dest))
    fn = _sweep_topology_batch_jit if batched else _sweep_topology_jit
    out = fn(ext, mem, intra, ext_frac, t_mask, topo, ov, dest, sim=sim_p)
    out = gs.gather(out, axis=1 if batched else 0)
    return _sharding_note(out, gs.describe())


# ---------------------------------------------------------------------------
# Workload-polymorphic sweeps + streaming sessions
# ---------------------------------------------------------------------------

def sweep_workload(specs, sim: SimConfig, *, seed: int = 0, keys=None,
                   dest: bool = False, devices=None, gen_chiplets=None,
                   **grids) -> dict:
    """Workload DSE: K traffic specs, ONE compiled executable.

    ::

        sweep_workload([traffic.ParsecSpec("dedup", n_intervals=64),
                        traffic.UniformSpec(n_intervals=32),
                        traffic.BurstySpec(n_intervals=48)], sim)

    Each spec (`traffic.TrafficSpec`, or a PARSEC app name) is generated
    under jit from `seed` (or an explicit [K]-row `keys` array) and the K
    traces — mixed lengths welcome — are padded to the longest T under a
    `t_mask` and run as a single vmapped scan. Results carry a leading [K]
    axis; lane k matches unpadded ``simulate(traffic.generate(specs[k],
    ...), sim)`` (tested per-arch at 1e-6).

    Workload zips with the other sweep axes: any TOPOLOGY_SWEEPABLE_FIELDS
    (n_chiplets / mesh_radix / gateway_positions / ...) or SWEEPABLE_FIELDS
    grids of length K pair element-wise with the specs, so "workload i on
    topology i with runtime knobs i" is still one compiled call.

    `dest=True` attaches each spec's destination matrix to its generated
    trace (`traffic.generate(..., dest=True)`), so every lane resolves
    actual source->destination gateway pressure — this is what separates
    transpose/tornado from uniform at the same mean load.

    `devices` (more than one — e.g. the fleet's global device list after
    `distributed.init_distributed`) shards the K workload axis with a 1-D
    NamedSharding: every lane-leading array (generated traces, topology
    grids, overrides, destination matrices) partitions over the "grid"
    mesh axis, K padded to a device multiple by repeating the last lane
    (logged; reported as `summary["pad_lanes"]` + a `"sharding"` dict and
    sliced off the results). A sharded run that fails raises.

    `gen_chiplets` pins the chiplet count traces are generated at (default:
    the largest `n_chiplets` in the grid). An emulated-host worker running
    a slice of a bigger grid passes the FULL grid's maximum here (plus the
    full run's sliced `keys`), so its lanes reproduce the full run's rows
    bit-for-bit even when its slice misses the global maximum.
    """
    specs = [traffic.as_spec(s) for s in specs]
    if not specs:
        raise ValueError("sweep_workload() needs at least one traffic spec")
    k = len(specs)
    if keys is None:
        keys = jax.random.split(jax.random.PRNGKey(seed), k)
    elif len(keys) != k:
        raise ValueError(f"{len(keys)} keys for {k} specs")
    for name, v in grids.items():
        n = _grid_len(name, v)
        if n != k:
            raise ValueError(
                f"grid {name!r} has length {n} but {k} workload specs "
                f"were given — workload zips element-wise with every grid")

    devices = list(devices) if devices is not None else None
    topo_grids = {g: v for g, v in grids.items()
                  if g in TOPOLOGY_SWEEPABLE_FIELDS}
    if topo_grids:
        c_gen = max(int(c) for c in topo_grids.get(
            "n_chiplets", [sim.cfg.n_chiplets]))
        if gen_chiplets is not None:
            if int(gen_chiplets) < c_gen:
                raise ValueError(
                    f"gen_chiplets={gen_chiplets} is smaller than the "
                    f"grid's largest n_chiplets ({c_gen})")
            c_gen = int(gen_chiplets)
        gen_cfg = sim.cfg.with_topology(n_chiplets=c_gen)
        traces = [traffic.generate(s, ky, gen_cfg, dest=dest)
                  for s, ky in zip(specs, keys)]
        batch = stack_traces(traces, pad=True)
        args, sim_p = _sweep_topology_operands(batch, sim, grids)
        if devices is not None and len(devices) > 1:
            return _shard_workload(
                args, devices,
                lambda a, _: _sweep_workload_topo_jit(*a, sim=sim_p))
        return _sweep_workload_topo_jit(*args, sim=sim_p)

    unknown = set(grids) - set(SWEEPABLE_FIELDS)
    if unknown:
        raise ValueError(
            f"non-sweepable fields: {sorted(unknown)} (topology: "
            f"{TOPOLOGY_SWEEPABLE_FIELDS}, runtime: {SWEEPABLE_FIELDS})")
    ov = {g: jnp.asarray(v) for g, v in grids.items()}
    traces = [traffic.generate(s, ky, sim.cfg, dest=dest)
              for s, ky in zip(specs, keys)]
    batch = stack_traces(traces, pad=True)
    ext, mem, intra, ext_frac, t_mask, dmat = _trace_arrays(batch)
    tables = selection_tables_jax(sim.cfg)
    if devices is not None and len(devices) > 1:
        return _shard_workload(
            (ext, mem, intra, ext_frac, t_mask, ov, dmat), devices,
            lambda a, rep: _sweep_workload_jit(
                a[0], a[1], a[2], a[3], a[4], rep[0], a[5], a[6], sim=sim),
            replicated=(tables,))
    return _sweep_workload_jit(ext, mem, intra, ext_frac, t_mask,
                               tables, ov, dmat, sim=sim)


def _shard_workload(args, devices, call, replicated=()):
    """Shard every lane-leading array of a workload sweep over `devices`.

    `args` is a tuple of leading-K pytrees (None leaves welcome);
    `replicated` holds fleet-global extras (e.g. selection tables).
    `call(sharded_args, replicated_extras)` launches the jitted entry
    point. Returns the gathered result dict with sharding metadata.
    """
    from repro.core.distributed import GridSharding

    k = int(args[0].shape[0])
    gs = GridSharding(k, devices=devices)
    out = call(gs.shard(args), gs.replicate(replicated))
    out = gs.gather(out)
    return _sharding_note(out, gs.describe())


class SimSession:
    """Streaming simulation session: unbounded traces at fixed memory.

    ::

        session = SimSession.init(sim)
        for chunk in online_trace_chunks:        # each a trace dict
            out = session.step_chunk(chunk)      # records + chunk summary
        total = session.summary()                # whole-stream summary

    The controller / PROWAVES / activity state persists across chunks (the
    carry is donated to the chunked executable, so steady-state streaming
    reuses its buffers in place), which makes a chunked run equivalent to
    one-shot `simulate` on the concatenated trace: per-interval records
    bit-match, and the running summary matches up to float re-association
    of the partial sums. Chunks of equal length share one compiled
    executable; `engine_stats()` shows one scan-body trace per chunk
    shape. A chunk's fault frame (faults.attach_faults) is an optional
    `faults` pytree: its own compiled program when present.
    """

    def __init__(self, sim: SimConfig, state: SimState, tables: dict):
        self.sim = sim
        self._state = state
        self._tables = tables
        self._sums = None
        self.placement = normalize_placement(
            resolve_gateway_positions(sim.cfg), sim.cfg)

    @classmethod
    def init(cls, sim: SimConfig) -> "SimSession":
        """Open a session with a fresh simulation state for `sim`."""
        return cls(sim, _initial_state(sim), selection_tables_jax(sim.cfg))

    def swap_placement(self, positions) -> None:
        """Live gateway re-placement between chunks (zero recompile).

        Placement reaches the executable only through the traced selection
        tables, so swapping in tables for a new placement reuses every
        cached chunk executable — this is what makes closed-loop recovery
        (serve.resilience.ResilienceRuntime) cheap at run time. The caller
        is responsible for charging the physical cost
        (faults.placement_reconfig_cost); the carried controller/NoC state
        streams on uninterrupted, modeling an in-flight reconfiguration.
        """
        p = normalize_placement(positions, self.sim.cfg)
        self._tables = selection_tables_jax(
            self.sim.cfg.with_placement(p))
        self.placement = p

    @property
    def intervals_seen(self) -> int:
        """Valid (unmasked) intervals consumed so far."""
        return 0 if self._sums is None \
            else int(self._sums["valid_intervals"])

    def step_chunk(self, chunk: dict) -> dict:
        """Consume one trace chunk; returns its records + chunk summary.

        `chunk` is an ordinary (unbatched) trace dict — `traffic.pad_trace`
        output with a `t_mask` is fine, e.g. a partial chunk padded to the
        session's steady chunk length so it reuses the same executable.
        Masked intervals freeze the carry (the controller never reacts to
        padded idle epochs), so padding mid-stream is exact too.
        """
        ext, mem, intra, ext_frac, t_mask, dest = _trace_arrays(chunk)
        if ext.ndim != 2:
            raise ValueError(
                f"step_chunk takes one unbatched trace chunk "
                f"(ext_load [T, C]), got ext_load {ext.shape}")
        self._state, recs, sums = _session_chunk_jit(
            self._state, ext, mem, intra, ext_frac, t_mask, self._tables,
            dest, _trace_faults(chunk), sim=self.sim)
        self._sums = sums if self._sums is None else jax.tree.map(
            lambda a, b: a + b, self._sums, sums)
        return {"records": recs,
                "summary": _summary_from_sums(sums, self.sim.cfg.n_chiplets)}

    def summary(self) -> dict:
        """Running summary over every interval streamed so far."""
        if self._sums is None:
            raise ValueError("summary() before any step_chunk() — the "
                             "session has consumed no intervals yet")
        return _summary_from_sums(self._sums, self.sim.cfg.n_chiplets)


def simulate_stream(chunks, sim: SimConfig) -> dict:
    """Drive a fresh `SimSession` over an iterable of trace chunks.

    Convenience wrapper for offline chunked runs: returns the final
    whole-stream summary plus the session (for further streaming).
    """
    session = SimSession.init(sim)
    n = 0
    for chunk in chunks:
        session.step_chunk(chunk)
        n += 1
    if n == 0:
        raise ValueError("simulate_stream() got an empty chunk iterable")
    return {"summary": session.summary(), "chunks": n, "session": session}


# ---------------------------------------------------------------------------
# Continuous-batching session packing (repro.serve.engine.SessionServer)
# ---------------------------------------------------------------------------

def init_session_states(sim: SimConfig, lanes: int) -> SimState:
    """Batched fresh session carries: a SimState pytree with leading [lanes].

    Every lane starts from the same `_initial_state` a standalone
    `SimSession.init` would hold, so lane k of the batched tick replays a
    standalone session exactly (the server resets a lane to row k of a
    fresh batch on every admission).
    """
    if lanes < 1:
        raise ValueError(f"lanes must be >= 1, got {lanes}")
    one = _initial_state(sim)
    return jax.tree.map(lambda a: jnp.stack([a] * lanes), one)


def session_tick(states: SimState, batch: dict, tables: dict,
                 sim: SimConfig, frame=None):
    """Advance B packed session lanes one chunk: ONE vmapped executable.

    `batch` is a lane-stacked chunk dict: ext_load [B, T, C], mem_load
    [B, T], int_load [B, T, C], ext_frac [B], t_mask [B, T]. Lane k steps
    exactly like `SimSession.step_chunk` on the same chunk (bit-parity on
    the CPU pinned by tests/test_serve.py; 1e-6 on a TPU, where the
    batched reductions may run in another order); an all-masked lane
    freezes its carry and contributes zero to every sum, so the server can
    park empty, retrying, or draining lanes without changing the
    executable's shape.

    `frame` (optional) is ONE fault frame (gw_ok [T, C, G] / stuck_on
    [T, C, G] / drift_db [T]) shared by every lane — faults live on
    hardware time, not session time. It is an optional `faults` pytree:
    its own compiled program when present, so clean ticks keep theirs and
    their exact numerics.

    An optional `batch["dest"]` [B, C, C] (per-lane destination matrices,
    e.g. from `stack_traces` over `generate(..., dest=True)` chunks) routes
    every lane through the destination-aware latency path; absent, the
    tick bit-matches the pre-dest executable.

    Returns (new_states, records, sums), each with a leading [B] axis.
    The carry is NOT donated: the caller may keep the previous states
    pytree to roll back lanes whose step failed (retry path).
    """
    return _session_tick_jit(
        *_session_tick_operands(states, batch, tables, frame), sim=sim)


def _session_tick_operands(states: SimState, batch: dict, tables: dict,
                           frame: Optional[dict] = None) -> tuple:
    """The operands `session_tick` hands `_session_tick_jit` (runtime.cache
    lowers the same)."""
    ext = jnp.asarray(batch["ext_load"])
    mem = jnp.asarray(batch["mem_load"])
    intra = jnp.asarray(batch["int_load"])
    ext_frac = jnp.asarray(batch["ext_frac"])
    t_mask = jnp.asarray(batch["t_mask"], jnp.float32)
    dest = batch.get("dest")
    dest = None if dest is None else jnp.asarray(dest, jnp.float32)
    if ext.ndim != 3 or mem.ndim != 2 or t_mask.ndim != 2:
        raise ValueError(
            f"session_tick takes lane-stacked chunks (ext_load [B, T, C], "
            f"mem_load [B, T], t_mask [B, T]); got ext_load {ext.shape}, "
            f"mem_load {mem.shape}, t_mask {t_mask.shape}")
    flt = None if frame is None \
        else _fault_xs(frame, int(mem.shape[1]), "the tick chunk")
    return (states, ext, mem, intra, ext_frac, t_mask, tables, dest, flt)


def session_sums_zero() -> dict:
    """The additive identity of `_record_sums` totals (a session that has
    served nothing yet): partial summaries of never-served sessions come
    out well-formed instead of raising. Host `np.float32` zeros: a served
    session's sums accumulate on the host in float32."""
    return {k: np.float32(0.0)
            for k in ("latency", "power_mw", "energy", "gateways",
                      "wavelengths", "saturated", "reconfig_nj",
                      "valid_intervals")}


def summary_from_sums(sums: dict, n_chiplets: int) -> dict:
    """Public summary reduction over accumulated `_record_sums` totals —
    the valid-intervals-only means every session summary (complete OR
    partial) is computed from."""
    return _summary_from_sums(sums, n_chiplets)


# ---------------------------------------------------------------------------
# Placement-polymorphic sweeps + compiled placement search (PlaceIT-style)
# ---------------------------------------------------------------------------

def sweep_placement(trace: dict, sim: SimConfig, placements, **grids) -> dict:
    """Gateway-placement DSE: K candidate placements, ONE compiled scan.

    ::

        sweep_placement(tr, sim, [None,                      # default edges
                                  ((1, 1), (2, 2), (1, 2), (2, 1)),
                                  ((0, 0), (3, 3), (0, 3), (3, 0))])

    Each placement is a tuple of (x, y) router coordinates in activation
    order (None = the default edge scheme). Placement data reaches the
    executable purely through traced per-point tables (hop means + access-
    waveguide loss), so the K placements share one jit cache entry per
    (shape, config, K) — re-sweeping different candidates of the same
    population size re-traces nothing, which is what makes the generation
    loop of `search_placement` compile-free after round one.

    Composes with the other sweep axes: any TOPOLOGY_SWEEPABLE_FIELDS /
    SWEEPABLE_FIELDS grids of the same length K zip in (`sweep_placement`
    is sugar for ``sweep_topology(..., gateway_positions=placements)``).
    Lane k matches unpadded `simulate` with
    ``NetworkConfig(gateway_positions=placements[k])`` (tested per-arch).
    """
    return sweep_topology(trace, sim, gateway_positions=list(placements),
                          **grids)


def sweep_placement_batch(traces, sim: SimConfig, placements,
                          **grids) -> dict:
    """N traces x K placements in ONE compiled call ([N, K] results)."""
    return sweep_topology_batch(traces, sim,
                                gateway_positions=list(placements), **grids)


def _placement_scores(summary: dict, inter_latency: np.ndarray,
                      objective: str) -> np.ndarray:
    """Per-lane scalar objective from device_get'd sweep results ([K])."""
    check_placement_objective(objective)
    if objective == "inter_latency":
        # Per-interval traffic-weighted inter-chiplet latency, [K, T] -> [K].
        return np.mean(inter_latency, axis=-1)
    return np.asarray(
        summary[PLACEMENT_OBJECTIVE_ALIASES.get(objective, objective)])


def search_placement(trace: dict, sim: SimConfig, *,
                     objective: str = "inter_latency",
                     generations: int = 10, population: int = 12,
                     seed: int = 0, init=None, temperature: float = 0.05,
                     cooling: float = 0.7, restart_frac: float = 0.25,
                     engine: str = "device",
                     blocked_positions=None) -> dict:
    """PlaceIT-style annealed gateway-placement search.

    Greedy/simulated-annealing hybrid: candidate placements (single-gateway
    moves around the incumbent, spread-reordered by the controller
    activation rule, plus random restarts) are scored per generation at
    fixed population size, with annealed acceptance of the incumbent and an
    elitist best over everything ever scored. The default edge scheme is
    always scored in generation 0, so `best_score <= default_score` when
    `init` is None.

    Two engines share these semantics:

      * `engine="device"` (default) — the whole search is ONE compiled
        `lax.scan` (repro.core.search.search_placement_device): proposals,
        traceable placement tables, scoring, acceptance and history all
        stay on device; a search is a single dispatch with zero host
        round-trips between generations (`engine_stats()` shows one
        scan-body trace and one `search_dispatches`). For parallel chains
        see `search_placement_islands`.
      * `engine="host"` — the PR-3 loop, retained as the parity oracle:
        numpy proposals, one `sweep_placement` call per generation (still
        one compiled executable across the search), and ONE
        `jax.device_get` of the summary pytree per generation.

    Both engines are deterministic per seed; their PRNG streams differ
    (jax.random vs numpy RandomState), so they explore different — equally
    valid — trajectories from the same seed.

    Returns {best_placement, best_score, best_summary, default_placement,
    default_score, improvement_frac, history} with one history entry per
    generation (the latency/power/energy trajectory of the search).

    `blocked_positions` excludes router coordinates (e.g. failed hardware
    reported by faults.FaultInjector) from the whole proposal space —
    restarts, mutations and the scored default all avoid them. An `init`
    that occupies a blocked router raises: repair it first
    (search.repair_placement).
    """
    if engine == "device":
        from repro.core.search import search_placement_device

        return search_placement_device(
            trace, sim, objective=objective, generations=generations,
            population=population, seed=seed, init=init,
            temperature=temperature, cooling=cooling,
            restart_frac=restart_frac, blocked_positions=blocked_positions)
    if engine != "host":
        raise ValueError(f"unknown engine {engine!r} (use 'device' or "
                         f"'host')")
    if population < 2:
        raise ValueError("population must be >= 2 (incumbent + candidates)")
    if generations < 1:
        raise ValueError("generations must be >= 1")
    cfg = sim.cfg
    gmax = cfg.max_gateways_per_chiplet
    blocked = {(int(x), int(y)) for (x, y) in (blocked_positions or ())}
    from repro.core import topology as _topology
    coords = [(int(x), int(y)) for x, y in _topology.router_coords(cfg)
              if (int(x), int(y)) not in blocked]
    if len(coords) < gmax:
        raise ValueError(
            f"{len(blocked)} blocked routers leave only {len(coords)} "
            f"allowed positions for {gmax} gateways")
    rng = np.random.RandomState(seed)

    default_p = normalize_placement(resolve_gateway_positions(cfg), cfg)
    if set(default_p) & blocked:
        # Can't score a default that sits on dead hardware; fall back to a
        # repaired variant of it as the reference lane.
        from repro.core.search import repair_placement
        default_p = repair_placement(default_p, blocked, cfg)
    parent = default_p if init is None \
        else normalize_placement(init, cfg)
    if set(parent) & blocked:
        raise ValueError(
            f"init placement occupies blocked routers "
            f"{sorted(set(parent) & blocked)} — repair it first "
            f"(search.repair_placement)")

    def random_placement():
        idx = rng.choice(len(coords), size=gmax, replace=False)
        return normalize_placement([coords[i] for i in idx], cfg,
                                   order="spread")

    def mutate(p, moves):
        pos = list(p)
        occupied = set(pos)
        for _ in range(moves):
            i = int(rng.randint(len(pos)))
            free = [c for c in coords if c not in occupied]
            if not free:
                break
            occupied.remove(pos[i])
            pos[i] = free[int(rng.randint(len(free)))]
            occupied.add(pos[i])
        return normalize_placement(pos, cfg, order="spread")

    best_p, best_s, best_summary = None, np.inf, None
    default_s = None
    temp = temperature
    history = []
    for gen in range(generations):
        moves = 2 if gen < max(1, generations // 3) else 1
        cands = [parent]
        if gen == 0 and parent != default_p:
            cands.append(default_p)
        while len(cands) < population:
            cands.append(random_placement()
                         if rng.rand() < restart_frac else
                         mutate(parent, moves))
        out = sweep_placement(trace, sim, cands)
        # ONE device->host sync for everything this generation consumes
        # (scores, lane summary, history values) — per-key np.asarray calls
        # here used to cost several round-trips per generation.
        pulled = jax.device_get(
            {"summary": out["summary"],
             "inter_latency": out["records"]["mean_inter_latency"]})
        scores = _placement_scores(pulled["summary"],
                                   pulled["inter_latency"], objective)
        if gen == 0:
            default_s = float(scores[cands.index(default_p)]
                              if default_p in cands else scores[0])
        ibest = int(np.argmin(scores))
        if scores[ibest] < best_s:
            best_p, best_s = cands[ibest], float(scores[ibest])
            best_summary = {k: float(v[ibest])
                            for k, v in pulled["summary"].items()}
        # Annealed incumbent move: greedy downhill, probabilistic uphill.
        delta = float(scores[ibest] - scores[0])
        rel = delta / max(abs(float(scores[0])), 1e-12)
        accepted = delta < 0 or (temp > 0
                                 and rng.rand() < math.exp(-rel / temp))
        if accepted:
            parent = cands[ibest]
        history.append({
            "generation": gen,
            "parent_score": float(scores[0]),
            "best_candidate_score": float(scores[ibest]),
            "best_score": float(best_s),
            "accepted": bool(accepted),
            "latency": float(pulled["summary"]["mean_latency"][ibest]),
            "power_mw": float(pulled["summary"]["mean_power_mw"][ibest]),
            "energy": float(pulled["summary"]["mean_energy"][ibest]),
        })
        temp *= cooling

    return {"best_placement": best_p, "best_score": best_s,
            "best_summary": best_summary,
            "default_placement": default_p, "default_score": default_s,
            "improvement_frac": 1.0 - best_s / max(default_s, 1e-12),
            "objective": objective, "generations": generations,
            "population": population, "engine": "host", "history": history}


def simulate_all_archs(trace: dict, base: SimConfig = SimConfig()) -> dict:
    out = {}
    for arch in Arch:
        out[arch.value] = simulate(trace, base.with_arch(arch))["summary"]
    return out


def __getattr__(name):
    # Lazy re-export: repro.core.search imports this module, so a top-level
    # import here would be circular. Resolved on first attribute access.
    if name in ("search_placement_device", "search_placement_islands"):
        from repro.core import search as _search
        return getattr(_search, name)
    if name in ("search_codesign", "rescore_front_host"):
        from repro.core import pareto as _pareto
        return getattr(_pareto, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
