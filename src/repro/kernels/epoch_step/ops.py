"""Wrapper for the fused epoch-scan kernel: lax.scan contract in, out.

`epoch_run_pallas(state, xs, sim, tables, ...)` is a drop-in replacement for
``jax.lax.scan(make_step(...), state, xs)`` on the configurations the kernel
supports (Arch.RESIPI / RESIPI_ALL, unpadded topology, optional destination
matrix, optional fault frames). It pads the time axis to the chunk size and
the chiplet axis to the TPU lane width (compiled mode), launches ONE
`pl.pallas_call` for the whole trace, and reassembles the exact record dict
and final SimState the scan body would have produced (1e-6 parity pinned in
tests/test_epoch_kernel.py, t_mask freezing and fault frames included).

Used by simulator._scan_trace when `SimConfig.epoch_kernel` is set; every
other path — and the parity oracle (ref.py) — keeps the lax.scan body.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.constants import PHOTONIC_POWER
from repro.core.gateway_controller import ControllerState
from repro.core import topology
from repro.core.noc import uniform_mesh_mean_hops
from repro.kernels import resolve_interpret
from repro.kernels.epoch_step.kernel import (COL_FAILED, COL_LASER,
                                             COL_LATENCY, COL_MEAN_INTER,
                                             COL_POWER, COL_RECONFIG,
                                             COL_SATURATED, LANES, N_COLS,
                                             _epoch_kernel)


def epoch_run_pallas(state, xs, sim, tables: dict, *,
                     dest: Optional[jax.Array] = None, faulted: bool = False,
                     interpret: bool | None = None,
                     t_chunk: int | None = None) -> Tuple[object, dict]:
    """Run T intervals fused; returns (final SimState, records) like scan.

    Args:
      state: SimState carry (simulator._initial_state or a session carry).
      xs: the scan xs tuple — (ext [T, C], mem [T], intra [T, C], ext_frac
        [T], t_mask [T]) plus (gw_ok [T, C, G], stuck_on [T, C, G],
        drift_db [T]) when `faulted` — with loads already t_mask-multiplied
        (the _simulate_impl contract).
      sim: SimConfig; may carry traced sweep overrides in l_m, max/min
        gateways, buffer_sat, wavelengths (they ride the SMEM params row).
      tables: selection tables (src_hops / gw_loss_db per level).
      dest: optional [C, C] row-stochastic destination matrix.
      interpret: None = backend-aware (compiled on TPU), explicit bool to
        force; interpret mode skips lane padding like noc_step.
      t_chunk: intervals per grid step (default min(T, 128)).
    """
    from repro.core.simulator import Arch, SimState, _activity_mask

    if sim.arch not in (Arch.RESIPI, Arch.RESIPI_ALL):
        raise ValueError(f"epoch_step kernel supports RESIPI/RESIPI_ALL, "
                         f"got {sim.arch}")
    cfg = sim.cfg
    g_slots = cfg.max_gateways_per_chiplet
    mem_gws = cfg.memory_gateways
    if mem_gws < 1:
        raise ValueError("epoch_step kernel needs >= 1 memory gateway "
                         "(the kappa chain's constant tail)")

    ext, mem, intra, _ext_frac, t_mask = xs[:5]
    if faulted:
        gw_ok, stuck_on, drift = (jnp.asarray(a, jnp.float32)
                                  for a in xs[5:8])
    else:
        gw_ok = stuck_on = None
        drift = jnp.zeros(jnp.shape(mem), jnp.float32)
    ext = jnp.asarray(ext, jnp.float32)
    intra = jnp.asarray(intra, jnp.float32)
    mem = jnp.asarray(mem, jnp.float32)
    t_mask = jnp.asarray(t_mask, jnp.float32)
    t, c = ext.shape
    if t < 1:
        raise ValueError("epoch_step kernel needs at least one interval")

    interpret = resolve_interpret(interpret)
    pad_lanes = not interpret
    if t_chunk is None:
        t_chunk = min(t, 128)

    # --- time padding: masked tail intervals (frozen, zero records) -------
    t_pad = (-t) % t_chunk
    if t_pad:
        ext = jnp.pad(ext, ((0, t_pad), (0, 0)))
        intra = jnp.pad(intra, ((0, t_pad), (0, 0)))
        mem = jnp.pad(mem, (0, t_pad))
        drift = jnp.pad(drift, (0, t_pad))
        t_mask_p = jnp.pad(t_mask, (0, t_pad))
    else:
        t_mask_p = t_mask
    t_full = t + t_pad
    n_steps = t_full // t_chunk

    # --- lane padding: padded chiplets enter at g=1, zero load, masked ----
    pad = (-c) % LANES if pad_lanes else 0
    p = c + pad
    g0 = state.ctl.g.astype(jnp.float32)
    lmask = jnp.ones((c,), jnp.float32)
    if pad:
        ext = jnp.pad(ext, ((0, 0), (0, pad)))
        intra = jnp.pad(intra, ((0, 0), (0, pad)))
        g0 = jnp.pad(g0, (0, pad), constant_values=1.0)
        lmask = jnp.pad(lmask, (0, pad))
    use_dest = dest is not None
    if use_dest:
        dmat = jnp.asarray(dest, jnp.float32)
        if pad:
            dmat = jnp.pad(dmat, ((0, pad), (0, pad)))

    # Fault frames: [T, C, G] -> [G, T, P], padded lanes/intervals healthy
    # (gw_ok=1, stuck_on=0) so they behave exactly like clean padded lanes.
    if faulted:
        ok_k = jnp.transpose(gw_ok, (2, 0, 1))
        st_k = jnp.transpose(stuck_on, (2, 0, 1))
        if t_pad:
            ok_k = jnp.pad(ok_k, ((0, 0), (0, t_pad), (0, 0)),
                           constant_values=1.0)
            st_k = jnp.pad(st_k, ((0, 0), (0, t_pad), (0, 0)))
        if pad:
            ok_k = jnp.pad(ok_k, ((0, 0), (0, 0), (0, pad)),
                           constant_values=1.0)
            st_k = jnp.pad(st_k, ((0, 0), (0, 0), (0, pad)))

    # Runtime (possibly traced via sweep overrides) scalar knobs.
    params = jnp.stack([
        jnp.asarray(sim.ctl.l_m, jnp.float32),
        jnp.asarray(sim.ctl.max_gateways, jnp.float32),
        jnp.asarray(sim.ctl.min_gateways, jnp.float32),
        jnp.asarray(sim.noc.buffer_sat, jnp.float32),
        jnp.asarray(sim.wavelengths, jnp.float32),
    ])[None, :]
    srch = jnp.asarray(tables["src_hops"], jnp.float32)[None, :]
    gwdb = jnp.asarray(tables["gw_loss_db"], jnp.float32)[None, :]

    s_cols = LANES if pad_lanes else N_COLS
    noc = sim.noc
    pwr = PHOTONIC_POWER
    kernel = functools.partial(
        _epoch_kernel,
        t_chunk=t_chunk, n_steps=n_steps, n_chiplets=c, g_slots=g_slots,
        mem_gws=mem_gws, use_dest=use_dest, faulted=faulted,
        use_controller=sim.arch == Arch.RESIPI, s_cols=s_cols, n_lanes=p,
        interval=float(cfg.reconfig_interval_cycles),
        burstiness=float(noc.burstiness),
        rpc=float(noc.router_pipeline_cycles),
        flight=float(noc.photonic_flight_cycles),
        feed_links=float(noc.feed_links),
        flits=float(cfg.packet_flits),
        packet_bits=float(cfg.packet_bits),
        ser_k=float(cfg.link_gbps_per_wavelength / cfg.noc_freq_ghz),
        mesh_hops=float(uniform_mesh_mean_hops(cfg)),
        mesh_feed=2.0 * topology.feed_width(cfg),
        laser_mw=float(pwr.laser_mw_per_wavelength),
        tia_mw=float(pwr.tia_mw),
        tuning_mw=float(pwr.tuning_mw_per_mr),
        driver_mw=float(pwr.driver_mw),
        controller_mw=float((pwr.controller_lgc_uw * cfg.n_chiplets
                             + pwr.controller_inc_uw) / 1000.0),
        reconfig_nj=float(pwr.pcmc_reconfig_nj))

    # Per-interval scalar rows as [n_steps, 1, t_chunk]: one (1, 1, t_chunk)
    # SMEM block per grid step meets the tiling rule at every length.
    row_spec = functools.partial(pl.BlockSpec, (1, 1, t_chunk),
                                 lambda i: (i, 0, 0),
                                 memory_space=pltpu.SMEM)
    whole = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    chunk = lambda width: pl.BlockSpec((t_chunk, width), lambda i: (i, 0))
    in_specs = [
        chunk(p),                                             # ext
        chunk(p),                                             # intra
        row_spec(),                                           # mem
        row_spec(),                                           # t_mask
        row_spec(),                                           # drift_db
        pl.BlockSpec((1, 5), lambda i: (0, 0),
                     memory_space=pltpu.SMEM),                # params
        pl.BlockSpec((1, g_slots), lambda i: (0, 0),
                     memory_space=pltpu.SMEM),                # src_hops
        pl.BlockSpec((1, g_slots), lambda i: (0, 0),
                     memory_space=pltpu.SMEM),                # gw_loss_db
        whole((1, p)),                                        # g0
        whole((1, p)),                                        # lane mask
    ]
    inputs = [ext, intra, mem.reshape(n_steps, 1, t_chunk),
              t_mask_p.reshape(n_steps, 1, t_chunk),
              drift.reshape(n_steps, 1, t_chunk), params, srch, gwdb,
              g0[None, :], lmask[None, :]]
    if use_dest:
        in_specs.append(whole((p, p)))
        inputs.append(dmat)
    if faulted:
        fault_spec = pl.BlockSpec((g_slots, t_chunk, p), lambda i: (0, i, 0))
        in_specs += [fault_spec, fault_spec]
        inputs += [ok_k, st_k]

    scal, out_g, out_gdes, out_gwl, out_gfin = pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=[
            chunk(s_cols), chunk(p), chunk(p), chunk(p), whole((1, p)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_full, s_cols), jnp.float32),
            jax.ShapeDtypeStruct((t_full, p), jnp.float32),
            jax.ShapeDtypeStruct((t_full, p), jnp.float32),
            jax.ShapeDtypeStruct((t_full, p), jnp.float32),
            jax.ShapeDtypeStruct((1, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, p), jnp.float32)],
        interpret=interpret,
    )(*inputs)

    # --- records: exactly the scan body's per-interval dict ---------------
    lam_f = jnp.asarray(sim.wavelengths, jnp.float32)
    latency = scal[:t, COL_LATENCY]
    power = scal[:t, COL_POWER]
    recs = {
        "latency": latency,
        "power_mw": power,
        "laser_mw": scal[:t, COL_LASER],
        "energy": power * latency,
        "reconfig_nj": scal[:t, COL_RECONFIG],
        "g": out_g[:t, :c].astype(jnp.int32),
        "wavelengths": lam_f * jnp.ones((t, c), jnp.float32)
                       * t_mask[:, None],
        "gw_load": out_gwl[:t, :c],
        "mean_inter_latency": scal[:t, COL_MEAN_INTER],
        "saturated": scal[:t, COL_SATURATED] > 0.5,
    }
    if faulted:
        recs["g_desired"] = out_gdes[:t, :c].astype(jnp.int32)
        recs["failed_slots"] = scal[:t, COL_FAILED]

    # --- final carry: g trajectory end + derived activity chain -----------
    n_valid = jnp.sum(t_mask)
    any_valid = n_valid > 0
    g_fin = out_gfin[0, :c].astype(jnp.int32)
    if faulted:
        # Activity under the LAST VALID interval's fault frame (the scan
        # body's new_active at that step); all-masked traces keep the old
        # prev_active via the any_valid gate below.
        idx = (t - 1) - jnp.argmax(t_mask[::-1] > 0).astype(jnp.int32)
        ok_l, st_l = gw_ok[idx], stuck_on[idx]                 # [C, G]
        desired = (jnp.arange(g_slots)[None, :]
                   < g_fin[:, None]).astype(jnp.float32)
        lit = jnp.maximum(desired * ok_l, st_l * ok_l)
        mem_on = jnp.ones((mem_gws,), jnp.float32)
        new_prev = jnp.concatenate([lit.reshape(-1), mem_on]) > 0.5
    else:
        new_prev = _activity_mask(g_fin, sim)
    if sim.arch == Arch.RESIPI:
        ctl = ControllerState(
            g=jnp.where(any_valid, g_fin, state.ctl.g),
            packets_seen=jnp.where(any_valid,
                                   jnp.zeros_like(state.ctl.packets_seen),
                                   state.ctl.packets_seen),
            epoch=state.ctl.epoch + n_valid.astype(jnp.int32))
    else:
        ctl = state.ctl
    new_state = SimState(
        ctl=ctl, wavelengths=state.wavelengths,
        prev_active=jnp.where(any_valid, new_prev, state.prev_active))
    return new_state, recs
