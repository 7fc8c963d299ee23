"""Fused epoch-scan Pallas kernel: the whole RESIPI interval loop.

One kernel launch runs T reconfiguration intervals of the Level-1 simulator
(simulator.make_step for Arch.RESIPI / RESIPI_ALL, unpadded topology): the
per-interval queueing metrics (noc.NocModel), the PCM power model
(photonics.interposer_power_mw, "pcm" mode), the Eq. 5-7 gateway controller
and the Eq. 4 kappa-switch reconfiguration energy all execute inside one
`pl.pallas_call`, with the per-chiplet gateway count as the only carried
state (VMEM scratch across grid steps). The XLA `lax.scan` body stays the
parity oracle (ref.py, 1e-6 in interpret mode).

Grid: (T // t_chunk,). Per-chiplet arrays ride in VMEM lane-padded to 128
(compiled mode); per-interval scalars (mem load, t_mask, loss drift) ride in
SMEM like noc_step's cycle masks, as [n_steps, 1, t_chunk] arrays blocked
(1, 1, t_chunk): the block's last two dims equal the array's, which the
TPU's (8, 128) tiling rule accepts at any grid length, and only one chunk
sits in the 1 MiB of SMEM at a time. Runtime sweepable knobs (l_m,
max/min_gateways, buffer_sat, wavelengths) arrive as a small SMEM params
vector because `sweep` may trace them.

Padded-lane contract: a lane-padded chiplet enters with g=1 and zero load —
the controller can never raise it (load 0 <= l_m) nor lower it (t_n(1) = 0),
so it stays at g=1 forever, and every mean / chain-sum / switch-count masks
it out via the lane-validity vector. Time-padded intervals freeze the g
carry and record zeros, exactly like the scan body's t_valid freeze.

The kappa chain (photonics.kappa_schedule) is evaluated in closed form: the
chain is chiplet-major, so a slot's upstream-active count is a strictly-
lower-triangular matmul over per-chiplet totals plus a static within-row
prefix; memory-gateway kappas are constant (1/(M-i)) and never switch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128   # TPU lane width: the chiplet axis pads to this for compilation
_DB_TO_LN = math.log(10.0) / 10.0   # 10 ** (x / 10) == exp(x * _DB_TO_LN)
# Full f32 contraction on the MXU (the default may round operands to bf16),
# so the compiled kernel matches the f32 scan body.
_HIGHEST = jax.lax.Precision.HIGHEST

# out_scal column layout (wrapper slices by these indices)
COL_LATENCY = 0
COL_POWER = 1
COL_LASER = 2
COL_RECONFIG = 3
COL_MEAN_INTER = 4
COL_SATURATED = 5
COL_FAILED = 6
N_COLS = 8


def _epoch_kernel(*refs, t_chunk: int, n_steps: int, n_chiplets: int,
                  g_slots: int, mem_gws: int, use_dest: bool, faulted: bool,
                  use_controller: bool, s_cols: int, n_lanes: int,
                  interval: float, burstiness: float, rpc: float,
                  flight: float, feed_links: float, flits: float,
                  packet_bits: float, ser_k: float, mesh_hops: float,
                  mesh_feed: float, laser_mw: float, tia_mw: float,
                  tuning_mw: float, driver_mw: float, controller_mw: float,
                  reconfig_nj: float):
    it = iter(refs)
    ext_ref = next(it)
    intra_ref = next(it)
    mem_ref = next(it)
    tmask_ref = next(it)
    drift_ref = next(it)
    params_ref = next(it)
    srch_ref = next(it)
    gwdb_ref = next(it)
    g0_ref = next(it)
    lmask_ref = next(it)
    dest_ref = next(it) if use_dest else None
    gwok_ref = next(it) if faulted else None
    stuck_ref = next(it) if faulted else None
    scal_ref = next(it)
    g_out_ref = next(it)
    gdes_ref = next(it)
    gwl_ref = next(it)
    gfin_ref = next(it)
    g_scr = next(it)

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        g_scr[...] = g0_ref[...].astype(jnp.float32)

    # Runtime (possibly swept) scalars from SMEM.
    lm = params_ref[0, 0]
    maxg = params_ref[0, 1]
    ming = params_ref[0, 2]
    bsat = params_ref[0, 3]
    lam = params_ref[0, 4]

    lmask = lmask_ref[...].astype(jnp.float32)            # [1, P] real lanes
    c_f = float(n_chiplets)
    m_f = float(mem_gws)
    flits_f = jnp.float32(flits)
    dmat = dest_ref[...].astype(jnp.float32) if use_dest else None

    # Strictly-lower-triangular chain-prefix matrix: prefix = tot @ LT sums
    # the per-chiplet active totals of every chiplet EARLIER in the chain.
    # (Mosaic builds iotas in integers only.)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, n_lanes), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_lanes, n_lanes), 1)
    lt_mat = (rows < cols).astype(jnp.float32)

    # --- queueing closures (op-for-op noc.NocModel) ------------------------
    def md1(rho, service):
        rho_eff = jnp.clip(rho / bsat, 0.0, 0.995)
        return burstiness * rho_eff * service / (2.0 * (1.0 - rho_eff))

    ser = packet_bits / (lam * ser_k)        # serialization_cycles(lam)
    s_eff_gw = jnp.maximum(ser, flits_f)     # port_cycles == packet_flits

    def gateway_lat(load):
        rho = jnp.clip(load * s_eff_gw, 0.0, 1.0)
        return s_eff_gw + md1(rho, s_eff_gw) + flight

    def access_lat(hops, load, burst_scale=None):
        walk = hops * rpc
        rho_link = jnp.clip(load * flits / feed_links, 0.0, 1.0)
        wait = md1(rho_link, flits_f)
        if burst_scale is not None:
            wait = wait * burst_scale
        return walk + wait

    def kappa_of(lit):
        """Per-slot Eq. 4 kappas for a [G]-list of [1, P] lit masks.

        Chain order is chiplet-major (slot index minor), memory gateways
        last; their kappas are the constant 1/(M-i) and never switch, so
        only the C*G chiplet slots are returned.
        """
        lit_m = [l * lmask for l in lit]
        tot = lit_m[0]
        for l in lit_m[1:]:
            tot = tot + l
        gt = jnp.sum(tot) + m_f
        prefix = jax.lax.dot_general(
            tot, lt_mat, (((1,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)           # [1, P]
        run = jnp.zeros_like(tot)
        ks = []
        for s in range(g_slots):
            upstream = prefix + run
            denom = jnp.maximum(gt - upstream, 1.0)
            ks.append(jnp.where(lit_m[s] > 0.0, 1.0 / denom, 0.0))
            run = run + lit_m[s]
        return ks

    def interval_body(t, g):
        ext = ext_ref[t, :][None, :].astype(jnp.float32)        # [1, P]
        intra = intra_ref[t, :][None, :].astype(jnp.float32)    # [1, P]
        mem = mem_ref[0, 0, t].astype(jnp.float32)
        tm = tmask_ref[0, 0, t].astype(jnp.float32)
        drift = drift_ref[0, 0, t].astype(jnp.float32)

        # Desired / usable / lit slot masks per static slot index.
        des = [(jnp.float32(s) < g).astype(jnp.float32)
               for s in range(g_slots)]
        if faulted:
            ok = [gwok_ref[s, pl.ds(t, 1), :].astype(jnp.float32)
                  for s in range(g_slots)]
            st = [stuck_ref[s, pl.ds(t, 1), :].astype(jnp.float32)
                  for s in range(g_slots)]
            usable = [d * o for d, o in zip(des, ok)]
            lit = [jnp.maximum(u, s_ * o)
                   for u, s_, o in zip(usable, st, ok)]
            g_eff = usable[0]
            for u in usable[1:]:
                g_eff = g_eff + u
        else:
            usable = des
            lit = des
            g_eff = g

        # --- _interval_metrics -----------------------------------------
        g_eff_f = jnp.maximum(g_eff, 1.0)
        gw_load = ext / g_eff_f
        mem_gw = mem / m_f

        lev = jnp.maximum(g_eff, 1.0) - 1.0      # activation level index
        src = jnp.zeros_like(g)
        gdb = jnp.zeros_like(g)
        for s in range(g_slots):
            sel = (lev == jnp.float32(s)).astype(jnp.float32)
            src = src + srch_ref[0, s] * sel
            gdb = gdb + gwdb_ref[0, s] * sel
        mean_src = jnp.sum(src * lmask) / c_f
        access_db = jnp.sum(gdb * lmask) / c_f + drift

        if use_dest:
            # recv_j = sum_i ext_i * dest_ij and the fan-in concentration
            # phi_j = sum_i (ext_i * dest_ij)^2 / recv_j^2, both as row-vec
            # matmuls over the destination matrix (no [P, P] materialization
            # or transposes; the squared weight factors elementwise).
            recv = jax.lax.dot_general(
                ext, dmat, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                preferred_element_type=jnp.float32)           # [1, P]
            phi = (jax.lax.dot_general(
                ext * ext, dmat * dmat, (((1,), (0,)), ((), ())),
                precision=_HIGHEST, preferred_element_type=jnp.float32)
                   / jnp.maximum(recv * recv, 1e-12))
            burst_scale = (1.0 + (burstiness - 1.0) * phi) / burstiness
            dst_gw = recv / g_eff_f
            dst_leg = access_lat(src, dst_gw, burst_scale)    # [1, P]
            inter = (access_lat(src, gw_load) + gateway_lat(gw_load)
                     + jax.lax.dot_general(
                         dst_leg, dmat, (((1,), (1,)), ((), ())),
                         precision=_HIGHEST,
                         preferred_element_type=jnp.float32))
        else:
            recv = None
            inter = (access_lat(src, gw_load) + gateway_lat(gw_load)
                     + access_lat(mean_src * jnp.ones_like(src), gw_load))
        mem_lat = (access_lat(mean_src, mem_gw) + gateway_lat(mem_gw)
                   + access_lat(1.0, mem_gw))
        link_load = intra * flits / mesh_feed
        intra_lat = (mesh_hops * rpc + flits
                     + md1(jnp.clip(link_load, 0.0, 1.0), flits_f))

        tot_ext = jnp.sum(ext) + 1e-9
        tot_int = jnp.sum(intra) + 1e-9
        tot_mem = mem + 1e-9
        lat = (jnp.sum(inter * ext) + jnp.sum(intra_lat * intra)
               + mem_lat * tot_mem) / (tot_ext + tot_int + tot_mem)
        minter = jnp.sum(inter * ext) / tot_ext
        sat = jnp.max((gw_load * s_eff_gw > bsat).astype(jnp.float32))

        # --- power (pcm mode) ------------------------------------------
        n_lit = jnp.float32(0.0)
        for l in lit:
            n_lit = n_lit + jnp.sum(l * lmask)
        lit_w = (n_lit + m_f) * lam
        # 10 ** (dB / 10) as an exponential: Mosaic has no scalar powf.
        laser = lit_w * laser_mw * jnp.exp(access_db * _DB_TO_LN)
        tia = lit_w * tia_mw
        tuning = (lit_w + lit_w) * tuning_mw
        driver = lit_w * driver_mw
        total = laser + tia + tuning + driver + controller_mw

        # --- controller + reconfiguration energy -----------------------
        if use_controller:
            if use_dest:
                pressure = jnp.maximum(ext, recv)
            else:
                pressure = ext
            packets = pressure * interval
            if faulted:
                packets = packets * (g / g_eff_f)
            g1 = jnp.maximum(g, 1.0)
            load = packets / (interval * g1)
            inc = (load > lm) & (g < maxg)
            dec = (load < lm * (1.0 - 1.0 / g1)) & (g > ming)
            g_new = jnp.where(inc, g + 1.0, jnp.where(dec, g - 1.0, g))

            des_new = [(jnp.float32(s) < g_new).astype(jnp.float32)
                       for s in range(g_slots)]
            if faulted:
                lit_new = [jnp.maximum(d * o, s_ * o)
                           for d, s_, o in zip(des_new, st, ok)]
            else:
                lit_new = des_new
            k_old = kappa_of(lit)
            k_new = kappa_of(lit_new)
            switched = jnp.float32(0.0)
            for ko, kn in zip(k_old, k_new):
                switched = switched + jnp.sum(
                    (jnp.abs(kn - ko) > 1e-6).astype(jnp.float32) * lmask)
            reconf = switched * reconfig_nj
        else:
            g_new = g
            reconf = jnp.float32(0.0)

        if faulted:
            failed = jnp.float32(0.0)
            for d, o in zip(des, ok):
                failed = failed + jnp.sum(
                    d * (o < 0.5).astype(jnp.float32) * lmask)
        else:
            failed = jnp.float32(0.0)

        # --- per-interval records (t_valid-masked like the scan body) ---
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, s_cols), 1)
        vals = (lat * tm, total * tm, laser * tm, reconf * tm, minter * tm,
                sat * tm, failed * tm)
        row = jnp.zeros((1, s_cols), jnp.float32)
        for k, v in enumerate(vals):
            row = row + v * (lane == k).astype(jnp.float32)
        scal_ref[pl.ds(t, 1), :] = row
        g_out_ref[pl.ds(t, 1), :] = g_eff * tm
        gdes_ref[pl.ds(t, 1), :] = g * tm
        gwl_ref[pl.ds(t, 1), :] = gw_load * tm

        # Masked intervals freeze the controller carry.
        return tm * g_new + (1.0 - tm) * g

    g_final = jax.lax.fori_loop(0, t_chunk, interval_body, g_scr[...])
    g_scr[...] = g_final

    @pl.when(step == n_steps - 1)
    def _emit():
        gfin_ref[...] = g_scr[...]
