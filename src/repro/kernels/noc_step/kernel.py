"""Flit-level NoC router simulation Pallas kernel (Fig. 13 residency maps).

Fluid-flow flit model of one chiplet's mesh: per cycle, every router
forwards up to `link_rate` flits toward its gateway along a static next-hop
map (XY routing, selection tables from repro.core.selection), subject to
destination buffer space (backpressure, proportional sharing on contention);
gateway sinks drain at their optical-port service rate. The per-cycle update
is matmul-structured (one-hot next-hop matrix) so the inner loop runs on the
MXU; occupancy state lives in VMEM scratch across a whole time-chunk, and
the residency integral (sum of occupancy over cycles — the Fig. 13 metric)
accumulates across grid steps.

Grid: (T // t_chunk,). Inputs: arrivals [T, R] blocked per chunk. The
occupancy/residency state persists in scratch across sequential grid steps.

Validated in interpret mode against ref.reference_noc_run (lax.scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

LANES = 128   # TPU lane width: the router axis pads to this for compilation
# Full f32 contraction on the MXU (the default may round operands to bf16),
# so the compiled kernel matches the f32 reference.
_HIGHEST = jax.lax.Precision.HIGHEST


def _noc_kernel(arrivals_ref, tmask_ref, next_mat_ref, drain_ref, buf_ref,
                mask_ref, resid_ref, occ_final_ref, drained_ref,
                occ_scratch, resid_scratch, drained_scratch,
                *, t_chunk: int, link_rate: float, n_steps: int,
                tv_mask: bool = False):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        occ_scratch[...] = jnp.zeros_like(occ_scratch)
        resid_scratch[...] = jnp.zeros_like(resid_scratch)
        drained_scratch[...] = jnp.zeros_like(drained_scratch)

    nmat = next_mat_ref[...].astype(jnp.float32)      # [R, R] one-hot
    drain = drain_ref[...].astype(jnp.float32)        # [1, R] sink rates
    buf = buf_ref[...].astype(jnp.float32)            # [1, R] capacities
    # Static path: [1, R] lane validity, read once. Time-varying path
    # (tv_mask, the fault-injection contract): the ref holds this chunk's
    # [t_chunk, R] rows and each cycle reads its own row.
    mask_static = None if tv_mask else mask_ref[...].astype(jnp.float32)

    def cycle(t, carry):
        occ0, resid, drained = carry
        arr = arrivals_ref[t, :][None, :].astype(jnp.float32)   # [1, R]
        # Per-cycle time-validity scalar (SMEM): a masked cycle freezes
        # the whole network state, so time-padded batches match their
        # unpadded originals exactly.
        tm = tmask_ref[0, 0, t].astype(jnp.float32)
        # Dead-lane enforcement: invalid (padded or faulted-this-cycle)
        # lanes can never hold or emit flits, whatever the caller put in
        # their arrival/buffer slots.
        mask = mask_ref[t, :][None, :].astype(jnp.float32) if tv_mask \
            else mask_static
        occ = (occ0 + arr) * mask
        send = jnp.minimum(occ, link_rate) * jnp.sign(
            jnp.sum(nmat, axis=1))[None, :]                     # routers only
        # desired inflow at each destination: send @ nmat  ([1,R]@[R,R])
        inflow_want = jax.lax.dot_general(
            send, nmat, (((1,), (0,)), ((), ())),
            precision=_HIGHEST,
            preferred_element_type=jnp.float32)                 # [1, R]
        space = jnp.maximum(buf - occ, 0.0)
        scale_dst = jnp.where(inflow_want > 0.0,
                              jnp.minimum(1.0, space / jnp.maximum(
                                  inflow_want, 1e-9)), 0.0)     # [1, R]
        # per-source allowed send = send * scale[next(source)]
        scale_src = jax.lax.dot_general(
            scale_dst, nmat, (((1,), (1,)), ((), ())),
            precision=_HIGHEST,
            preferred_element_type=jnp.float32)                 # [1, R]
        moved = send * scale_src
        inflow = jax.lax.dot_general(
            moved, nmat, (((1,), (0,)), ((), ())),
            precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        # Flits routed INTO a dead lane are lost at the broken link (the
        # sender already moved them out); on clean paths nothing routes
        # into a padded lane, so this multiply is exactly x 1.0 there.
        occ = occ - moved + inflow * mask
        sunk = jnp.minimum(occ, drain)
        occ = occ - sunk
        return (tm * occ + (1.0 - tm) * occ0,
                resid + tm * occ, drained + tm * sunk)

    occ, resid, drained = jax.lax.fori_loop(
        0, t_chunk, cycle,
        (occ_scratch[...], resid_scratch[...], drained_scratch[...]))
    occ_scratch[...] = occ
    resid_scratch[...] = resid
    drained_scratch[...] = drained

    @pl.when(step == n_steps - 1)
    def _emit():
        resid_ref[...] = resid_scratch[...]
        occ_final_ref[...] = occ_scratch[...]
        drained_ref[...] = drained_scratch[...]


def noc_run_pallas(arrivals: jax.Array, next_mat: jax.Array,
                   drain_rate: jax.Array, buf_cap: jax.Array,
                   *, valid_mask: jax.Array | None = None,
                   valid_mask_t: jax.Array | None = None,
                   t_mask: jax.Array | None = None,
                   t_chunk: int = 256, link_rate: float = 1.0,
                   interpret: bool | None = None,
                   pad_lanes: bool | None = None):
    """Run T cycles of the flit model.

    Args:
      arrivals: [T, R] flits injected per cycle per node.
      next_mat: [R, R] one-hot routing matrix (rows: source; sinks all-zero).
      drain_rate: [R] flits/cycle sunk at gateway nodes (0 elsewhere).
      buf_cap: [R] buffer capacity in flits.
      valid_mask: [R] 1/0 lane-validity mask (None = all valid). Invalid
        lanes are DEAD: occupancy is forced to zero every cycle, so they
        never send, receive, or accumulate residency even when a padded
        batch layout leaves garbage in their arrival/buffer slots. This is
        the topology-batching contract — padded router lanes are dead
        lanes, not zero-traffic routers.
      valid_mask_t: [T, R] 1/0 TIME-VARYING lane-validity mask (None =
        static lanes only). Row t ANDs with `valid_mask` for cycle t: a
        lane whose row goes to 0 mid-run (a fault firing) drops its flits
        and is dead — zero send/hold/residency — for exactly those cycles,
        then revives empty. An all-ones mask takes the same code path but
        multiplies by 1.0, so "fault masked at t == T" matches the static
        fault-free run bit-for-bit (the fault-parity smoke contract).
      t_mask: [T] 1/0 cycle-validity mask (None = all valid). Masked
        cycles FREEZE the network: no arrivals, no movement, no drain, no
        residency accumulation — so mixed-length cycle batches can pad the
        time axis and still match their unpadded originals exactly (the
        ragged-T contract of the epoch engine, at flit granularity). When
        T is not a multiple of `t_chunk`, the wrapper pads the tail with
        masked cycles automatically.
      interpret: None = backend-aware (compiled on TPU), or explicit bool.
      pad_lanes: pad the router axis up to the 128-lane boundary. Defaults
        to on whenever the kernel compiles (Mosaic requires lane-aligned
        blocks); lane-pad nodes extend the validity mask with zeros.

    Returns (residency_integral [R], final_occupancy [R], drained [R]).
    """
    interpret = resolve_interpret(interpret)
    if pad_lanes is None:
        pad_lanes = not interpret
    t, r_in = arrivals.shape
    if valid_mask is None:
        valid_mask = jnp.ones((r_in,), jnp.float32)
    valid_mask = valid_mask.astype(jnp.float32)
    if t_mask is None:
        t_mask = jnp.ones((t,), jnp.float32)
    t_mask = t_mask.astype(jnp.float32)
    tv = valid_mask_t is not None
    if tv:
        if valid_mask_t.shape != (t, r_in):
            raise ValueError(
                f"valid_mask_t must be [T, R] = {(t, r_in)}, got "
                f"{valid_mask_t.shape}")
        # The static lane mask ANDs in here; the kernel sees ONE combined
        # per-cycle mask plane.
        mask_in = valid_mask_t.astype(jnp.float32) * valid_mask[None, :]
    t_pad = (-t) % t_chunk
    if t_pad:       # tail cycles arrive masked-out: frozen, zero residency
        arrivals = jnp.pad(arrivals, ((0, t_pad), (0, 0)))
        t_mask = jnp.pad(t_mask, (0, t_pad))
        if tv:
            mask_in = jnp.pad(mask_in, ((0, t_pad), (0, 0)))
        t += t_pad
    pad = (-r_in) % LANES if pad_lanes else 0
    if pad:
        arrivals = jnp.pad(arrivals, ((0, 0), (0, pad)))
        next_mat = jnp.pad(next_mat, ((0, pad), (0, pad)))
        drain_rate = jnp.pad(drain_rate, (0, pad))
        buf_cap = jnp.pad(buf_cap, (0, pad))
        valid_mask = jnp.pad(valid_mask, (0, pad))
        if tv:
            mask_in = jnp.pad(mask_in, ((0, 0), (0, pad)))
    r = r_in + pad
    n_steps = t // t_chunk
    if not tv:
        mask_in = valid_mask[None, :]
    kernel = functools.partial(_noc_kernel, t_chunk=t_chunk,
                               link_rate=link_rate, n_steps=n_steps,
                               tv_mask=tv)
    mask_spec = pl.BlockSpec((t_chunk, r), lambda i: (i, 0)) if tv \
        else pl.BlockSpec((1, r), lambda i: (0, 0))
    resid, occ, drained = pl.pallas_call(
        kernel,
        grid=(n_steps,),
        in_specs=[
            pl.BlockSpec((t_chunk, r), lambda i: (i, 0)),
            # per-cycle validity scalars ride in SMEM, R times smaller than
            # a [T, R] mask, as [n_steps, 1, t_chunk] blocked (1, 1,
            # t_chunk): last two block dims equal the array's, so the
            # (8, 128) tiling rule holds at every grid length
            pl.BlockSpec((1, 1, t_chunk), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((r, r), lambda i: (0, 0)),
            pl.BlockSpec((1, r), lambda i: (0, 0)),
            pl.BlockSpec((1, r), lambda i: (0, 0)),
            mask_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, r), lambda i: (0, 0)),
            pl.BlockSpec((1, r), lambda i: (0, 0)),
            pl.BlockSpec((1, r), lambda i: (0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((1, r), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((1, r), jnp.float32)] * 3,
        interpret=interpret,
    )(arrivals, t_mask.reshape(n_steps, 1, t_chunk), next_mat,
      drain_rate[None, :], buf_cap[None, :], mask_in)
    return resid[0, :r_in], occ[0, :r_in], drained[0, :r_in]
