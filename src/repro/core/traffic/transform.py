"""Trace transforms: validation, slicing, concatenation, time padding.

Every transform validates its inputs up front (`validate_trace`), so a
malformed trace fails with a clear message here instead of deep inside a jit
trace. The time-padding helpers (`pad_trace` / `trace_length`) implement the
ragged-T contract: a padded trace carries a `t_mask` [T] validity vector and
the engine guarantees masked intervals contribute exactly zero to every
latency/power/energy reduction (see simulator._simulate_impl).
"""
from __future__ import annotations

import jax.core as jax_core
import jax.dtypes as jax_dtypes
import jax.numpy as jnp
import numpy as np

from repro.runtime import spans

# The array keys every trace must carry (plus the "app" label, the ragged-T
# "t_mask", and the optional "dest" destination matrix). "dest" is [C, C] and
# time-free: it must never be sliced/padded along T (with C == T the shape
# check alone could not tell them apart), so it lives in the meta set and is
# carried whole by every transform; only `slice_trace` touches it (chiplet
# axis) and `concat_traces` mixes it load-weighted.
TRACE_KEYS = ("ext_load", "mem_load", "int_load", "ext_frac")
_META_KEYS = ("app", "t_mask", "dest")


def _renormalize_rows(dest):
    """Re-normalize a destination matrix's rows after masking/slicing.

    Rows whose mass was entirely masked away go to all-zero (their sources
    inject nothing in that view, so the row is never consulted).
    """
    dest = jnp.asarray(dest, jnp.float32)
    row = jnp.sum(dest, axis=-1, keepdims=True)
    return jnp.where(row > 0.0, dest / jnp.maximum(row, 1e-12), 0.0)


def validate_trace(trace, who: str = "trace") -> dict:
    """Check that `trace` is a well-formed trace dict; return it.

    Raises TypeError for non-dict inputs and ValueError naming any missing
    keys — the clear-error front door for every transform and engine entry
    point (a malformed trace used to fail deep inside the jit trace).
    """
    if not isinstance(trace, dict):
        raise TypeError(
            f"{who} must be a trace dict with keys {TRACE_KEYS} "
            f"(see repro.core.traffic.generate), got "
            f"{type(trace).__name__}: {trace!r:.80}")
    missing = [k for k in TRACE_KEYS if k not in trace]
    if missing:
        raise ValueError(
            f"{who} is missing {missing}; a trace dict needs {TRACE_KEYS} "
            f"(generate one with repro.core.traffic.generate / "
            f"generate_trace)")
    # Value sanity: NaN or negative injected loads only surface as garbage
    # summaries deep inside the compiled scan — reject them here, pre-jit.
    # Tracers (trace construction inside jit/vmap) have no values to check
    # and skip; concrete arrays (the common host-side path) are cheap to
    # scan once at the boundary.
    with spans.span("traffic.validate"):
        for k in TRACE_KEYS:
            v = trace[k]
            if isinstance(v, jax_core.Tracer):
                continue
            arr = np.asarray(v)
            if not np.issubdtype(arr.dtype, np.number):
                raise ValueError(
                    f"{who}[{k!r}] must be numeric, got dtype {arr.dtype}")
            if np.isnan(arr).any():
                raise ValueError(
                    f"{who}[{k!r}] contains NaN — injected loads must be "
                    f"finite (the compiled scan would silently propagate "
                    f"NaN into every summary)")
            if (arr < 0).any():
                raise ValueError(
                    f"{who}[{k!r}] contains negative values (min "
                    f"{float(arr.min()):g}) — loads are non-negative "
                    f"flit rates")
        d = trace.get("dest")
        if d is not None and not isinstance(d, jax_core.Tracer):
            arr = np.asarray(d)
            ext = trace["ext_load"]
            c = None if isinstance(ext, jax_core.Tracer) \
                else int(np.shape(np.asarray(ext))[-1])
            # Stacked batches (stack_traces) carry one leading [K] axis;
            # the trailing two dims must still be square and match the
            # chiplet axis.
            if arr.ndim not in (2, 3) or arr.shape[-2] != arr.shape[-1] \
                    or (c is not None and arr.shape[-1] != c):
                raise ValueError(
                    f"{who}['dest'] must be a square [C, C] destination "
                    f"matrix (optionally with one leading batch axis) "
                    f"matching the trace's chiplet axis"
                    f"{'' if c is None else f' (C={c})'}, got shape "
                    f"{arr.shape}")
            if not np.isfinite(arr).all() or (arr < 0).any():
                raise ValueError(
                    f"{who}['dest'] must be finite and non-negative (a "
                    f"row-stochastic destination distribution)")
    return trace


def trace_length(trace: dict) -> int:
    """Valid interval count: sum of `t_mask` if present, else the T axis."""
    validate_trace(trace)
    if "t_mask" in trace:
        return int(np.sum(np.asarray(trace["t_mask"]) > 0))
    return int(jnp.shape(trace["ext_load"])[0])


def slice_trace(trace: dict, n_chiplets: int) -> dict:
    """Restrict a trace to its first `n_chiplets` chiplet columns.

    The per-topology view used by topology sweeps: a trace generated at the
    grid's maximum chiplet count is narrowed per grid point. `mem_load` and
    `ext_frac` are chiplet-count-free and shared across grid points.
    """
    validate_trace(trace)
    c = trace["ext_load"].shape[-1]
    if n_chiplets > c:
        raise ValueError(f"trace has {c} chiplets, needs >= {n_chiplets}")
    out = dict(trace,
               ext_load=trace["ext_load"][..., :n_chiplets],
               int_load=trace["int_load"][..., :n_chiplets])
    if trace.get("dest") is not None:
        out["dest"] = _renormalize_rows(
            trace["dest"][..., :n_chiplets, :n_chiplets])
    return out


def _xp(a):
    """The array module a transform keeps `a` in: numpy for host arrays
    and numpy scalars, `jax.numpy` for everything else (device arrays,
    tracers, Python values)."""
    return np if isinstance(a, (np.ndarray, np.generic)) else jnp


def _as_array(a, dtype=None):
    """`a` as an array of its own kind, in the dtype `jnp.asarray` gives
    (a host float64 comes out float32, as it would on the device)."""
    xp = _xp(a)
    if dtype is None and xp is np:
        dtype = jax_dtypes.canonicalize_dtype(np.result_type(a))
    return xp.asarray(a, dtype)


def pad_trace(trace: dict, n_intervals: int) -> dict:
    """Zero-pad a trace's time axis to `n_intervals`, adding a `t_mask`.

    Padded tail intervals inject zero traffic and are masked out of every
    engine reduction, so a padded trace simulates identically to the
    original (the ragged-batching invariant, pinned per-arch in tests).
    Already-padded traces extend their existing mask. Each key keeps its
    array kind: host (numpy) inputs pad on the host, device arrays and
    tracers through `jax.numpy`; a missing mask follows `ext_load`.
    """
    validate_trace(trace)
    t = int(jnp.shape(trace["ext_load"])[0])
    if n_intervals < t:
        raise ValueError(f"cannot pad a {t}-interval trace down to "
                         f"{n_intervals} (use slice on the time axis "
                         f"explicitly instead)")
    mask = trace.get("t_mask")
    if mask is None:
        mask = _xp(trace["ext_load"]).ones((t,), np.float32)
    mask = _as_array(mask, np.float32)
    pad = n_intervals - t
    if pad == 0:
        return dict(trace, t_mask=mask)

    def _pad_time(a):
        a = _as_array(a)
        return _xp(a).pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

    out = dict(trace)
    for k in ("ext_load", "mem_load", "int_load"):
        out[k] = _pad_time(trace[k])
    out["t_mask"] = _pad_time(mask)
    # Carry any extra per-interval arrays along (leading axis == T).
    for k, v in trace.items():
        if k in TRACE_KEYS or k in _META_KEYS:
            continue
        if hasattr(v, "ndim") and getattr(v, "ndim", 0) >= 1 \
                and jnp.shape(v)[0] == t:
            out[k] = _pad_time(v)
    return out


def chunk_trace(trace: dict, size: int, *, pad: bool = False):
    """Yield consecutive `size`-interval chunks of a trace (last may be
    shorter — pass `pad=True` to zero-pad it to `size` with a `t_mask`,
    so every chunk reuses a streaming session's steady executable).

    Every per-interval key — the core loads, `t_mask`, and any extra array
    whose leading axis is T — is sliced; everything else is carried whole.
    Chunks keep the trace's array kind (see `pad_trace`): a host trace
    chunks and pads on the host, with no device round trip.
    The streaming companion to `SimSession.step_chunk` and the chunk feed
    of the continuous-batching `SessionServer` (fixed-shape lanes).
    """
    validate_trace(trace)
    if size < 1:
        raise ValueError(f"chunk size must be >= 1, got {size}")
    t = int(jnp.shape(trace["ext_load"])[0])
    per_t = [k for k, v in trace.items()
             if k in ("ext_load", "mem_load", "int_load", "t_mask")
             or (hasattr(v, "ndim") and getattr(v, "ndim", 0) >= 1
                 and k not in ("app", "dest") and jnp.shape(v)[0] == t)]
    for s in range(0, t, size):
        chunk = {k: (v[s:s + size] if k in per_t else v)
                 for k, v in trace.items()}
        yield pad_trace(chunk, size) if pad else chunk


def concat_traces(traces: list) -> dict:
    """Stitch traces back-to-back (Fig. 12 application-switch runs).

    `ext_frac` is the load-weighted mean of the segments' fractions (each
    segment weighted by its total ext load — an unweighted mean would let a
    near-idle segment drag the composite fraction). Keys outside the core
    trace schema are carried through: per-interval arrays (leading axis ==
    that segment's T) concatenate, segment-constant values must agree, and
    anything else raises instead of being silently dropped.
    """
    if not traces:
        raise ValueError("concat_traces() needs at least one trace")
    for i, tr in enumerate(traces):
        validate_trace(tr, who=f"traces[{i}]")
    lens = [int(jnp.shape(tr["ext_load"])[0]) for tr in traces]
    out = {k: jnp.concatenate([jnp.asarray(tr[k]) for tr in traces], axis=0)
           for k in ("ext_load", "mem_load", "int_load")}

    # Load-weighted ext_frac: sum_i f_i * L_i / sum_i L_i.
    weights = jnp.stack([jnp.sum(jnp.asarray(tr["ext_load"], jnp.float32))
                         for tr in traces])
    fracs = jnp.stack([jnp.asarray(tr["ext_frac"], jnp.float32)
                       for tr in traces])
    total = jnp.sum(weights)
    out["ext_frac"] = jnp.where(
        total > 0.0, jnp.sum(fracs * weights) / jnp.maximum(total, 1e-12),
        jnp.mean(fracs))
    out["app"] = "+".join(str(tr.get("app", "?")) for tr in traces)

    if any("t_mask" in tr for tr in traces):
        out["t_mask"] = jnp.concatenate(
            [jnp.asarray(tr.get("t_mask", jnp.ones((n,), jnp.float32)),
                         jnp.float32) for tr, n in zip(traces, lens)])

    if any(tr.get("dest") is not None for tr in traces):
        if not all(tr.get("dest") is not None for tr in traces):
            raise ValueError(
                "'dest' present in only some segments — concat_traces "
                "cannot stitch a partial destination matrix (attach one to "
                "every segment via generate(..., dest=True) or drop it)")
        # One composite matrix for the whole run: each segment's destination
        # rows weighted by its total ext load (mirrors the ext_frac mix),
        # then re-normalized to row-stochastic.
        dests = jnp.stack([jnp.asarray(tr["dest"], jnp.float32)
                           for tr in traces])
        w = jnp.where(total > 0.0, weights / jnp.maximum(total, 1e-12),
                      jnp.full_like(weights, 1.0 / len(traces)))
        out["dest"] = _renormalize_rows(
            jnp.sum(dests * w[:, None, None], axis=0))

    known = set(TRACE_KEYS) | set(_META_KEYS)
    extras = sorted(set().union(*(set(tr) for tr in traces)) - known)
    for k in extras:
        holders = [k in tr for tr in traces]
        if not all(holders):
            raise ValueError(
                f"key {k!r} present in only {sum(holders)}/{len(traces)} "
                f"segments — concat_traces cannot stitch a partial key "
                f"(drop it or add it to every segment)")
        vals = [tr[k] for tr in traces]
        if all(hasattr(v, "ndim") and getattr(v, "ndim", 0) >= 1
               and jnp.shape(v)[0] == n for v, n in zip(vals, lens)):
            out[k] = jnp.concatenate([jnp.asarray(v) for v in vals], axis=0)
        elif all(_values_equal(v, vals[0]) for v in vals[1:]):
            out[k] = vals[0]
        else:
            raise ValueError(
                f"key {k!r} differs across segments and is not a "
                f"per-interval array — concat_traces cannot merge it "
                f"(values: {[str(v)[:40] for v in vals]})")
    return out


def _values_equal(a, b) -> bool:
    if hasattr(a, "shape") or hasattr(b, "shape"):
        return np.array_equal(np.asarray(a), np.asarray(b))
    return a == b
