"""Trace generation: spec + PRNG key -> trace dict, under jit.

A trace is a dict of arrays over reconfiguration intervals:
  ext_load   [T, C] — inter-chiplet packet injection per chiplet (pkts/cycle)
  mem_load   [T]    — traffic to the 2 memory-controller gateways (pkts/cycle)
  int_load   [T, C] — intra-chiplet-only traffic (pkts/cycle per chiplet)
  ext_frac   []     — fraction of packets that cross the interposer
  app        str    — workload label (the spec's `name`)

`generate(spec, key, cfg)` is the single entry point. The spec and cfg are
static jit arguments (both frozen/hashable), the PRNG key is traced: one tiny
compiled generator per (spec, cfg), re-keying is compile-free, and the whole
workload axis stays reproducible by seed. GEM5 full-system traces are
unavailable offline (DESIGN.md §9.1), so the PARSEC path generates per-interval
chiplet traffic calibrated to the paper's own characterization (§4.2, §4.5);
the synthetic paths implement the canonical NoC workloads (specs.py).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constants import NETWORK, NetworkConfig
from repro.core.traffic.specs import (APP_NAMES, BurstySpec, HotspotSpec,
                                      ParsecSpec, PermutationSpec,
                                      TrafficSpec, UniformSpec, as_spec,
                                      permutation_destinations)
from repro.runtime import spans


def _lognormal_jitter(key: jax.Array, shape, cv: float) -> jax.Array:
    """Unit-mean lognormal multiplicative jitter with coefficient cv."""
    if cv <= 0.0:
        return jnp.ones(shape, jnp.float32)
    sigma = jnp.sqrt(jnp.log1p(cv ** 2))
    return jnp.exp(jax.random.normal(key, shape) * sigma - 0.5 * sigma ** 2)


def _package(ext: jax.Array, intra: jax.Array, ext_frac: float,
             mem_frac: float) -> dict:
    return {"ext_load": ext,
            "mem_load": mem_frac * jnp.sum(ext, axis=1),
            "int_load": intra,
            "ext_frac": jnp.float32(ext_frac)}


def _gen_parsec(spec: ParsecSpec, key: jax.Array,
                cfg: NetworkConfig) -> dict:
    """The calibrated PARSEC-like generator (op-for-op the pre-package
    `traffic.generate_trace`: same key splits, same math — seeded traces
    are unchanged up to jit fusion rounding, ~1e-7 relative)."""
    prof = spec.profile
    c = cfg.n_chiplets
    k_phase, k_jit, k_chip = jax.random.split(key, 3)

    t = jnp.arange(spec.n_intervals, dtype=jnp.float32)
    # Application phases: raised cosine keeps load non-negative and gives the
    # controller real transitions to track.
    phase = 1.0 + 0.5 * jnp.sin(2.0 * jnp.pi * t / prof.phase_period
                                + jax.random.uniform(k_phase) * 6.28)
    jitter = _lognormal_jitter(k_jit, (spec.n_intervals, c), prof.cv)
    # Mild static per-chiplet imbalance (placement effects).
    chip_w = 1.0 + 0.15 * jax.random.normal(k_chip, (c,))
    chip_w = jnp.clip(chip_w, 0.7, 1.3)

    ext = prof.mean_ext_load * phase[:, None] * jitter * chip_w[None, :]
    intra = ext * (1.0 - prof.ext_frac) / jnp.maximum(prof.ext_frac, 1e-6)
    return _package(ext, intra, prof.ext_frac, prof.mem_frac)


def _gen_uniform(spec: UniformSpec, key: jax.Array,
                 cfg: NetworkConfig) -> dict:
    ext = spec.mean_load * _lognormal_jitter(
        key, (spec.n_intervals, cfg.n_chiplets), spec.cv)
    intra = ext * (1.0 - spec.ext_frac) / spec.ext_frac
    return _package(ext, intra, spec.ext_frac, spec.mem_frac)


def _gen_hotspot(spec: HotspotSpec, key: jax.Array,
                 cfg: NetworkConfig) -> dict:
    c = cfg.n_chiplets
    n_hot = min(spec.n_hotspots, c)
    k_pick, k_jit = jax.random.split(key)
    jitter = _lognormal_jitter(k_jit, (spec.n_intervals, c), spec.cv)
    if n_hot >= c:                      # degenerate: everything is a hotspot
        w = jnp.ones((c,), jnp.float32)
    else:
        # Unit-mean spatial weights: the hotspot set carries hotspot_frac of
        # the total offered load, the rest share the remainder evenly.
        hot = jnp.zeros((c,), jnp.float32).at[
            jax.random.permutation(k_pick, c)[:n_hot]].set(1.0)
        w = (hot * (spec.hotspot_frac * c / n_hot)
             + (1.0 - hot) * ((1.0 - spec.hotspot_frac) * c / (c - n_hot)))
    ext = spec.mean_load * w[None, :] * jitter
    intra = ext * (1.0 - spec.ext_frac) / spec.ext_frac
    return _package(ext, intra, spec.ext_frac, spec.mem_frac)


def _gen_permutation(spec: PermutationSpec, key: jax.Array,
                     cfg: NetworkConfig) -> dict:
    c = cfg.n_chiplets
    dst = permutation_destinations(spec.pattern, c)
    self_paired = jnp.asarray(dst == np.arange(c), jnp.float32)
    jitter = _lognormal_jitter(key, (spec.n_intervals, c), spec.cv)
    offered = (spec.mean_load / spec.ext_frac) * jitter   # total load/chiplet
    # Self-paired chiplets keep their whole load on the local mesh; the rest
    # split ext_frac : 1-ext_frac between interposer and mesh.
    ext = spec.ext_frac * offered * (1.0 - self_paired)[None, :]
    intra = offered - ext
    return _package(ext, intra, spec.ext_frac, spec.mem_frac)


def _gen_bursty(spec: BurstySpec, key: jax.Array,
                cfg: NetworkConfig) -> dict:
    c = cfg.n_chiplets
    k0, k_chain, k_jit = jax.random.split(key, 3)
    duty = spec.duty
    on0 = jax.random.uniform(k0, (c,)) < duty     # stationary initial state
    u = jax.random.uniform(k_chain, (spec.n_intervals, c))

    def chain(on, u_t):
        on_next = jnp.where(on, u_t >= spec.p_off, u_t < spec.p_on)
        return on_next, on_next

    _, on = jax.lax.scan(chain, on0, u)           # [T, C] bool
    on_load = spec.mean_load / duty               # calibrated: E[ext]=mean
    jitter = _lognormal_jitter(k_jit, (spec.n_intervals, c), spec.cv)
    ext = on_load * on.astype(jnp.float32) * jitter
    intra = ext * (1.0 - spec.ext_frac) / spec.ext_frac
    return _package(ext, intra, spec.ext_frac, spec.mem_frac)


_GENERATORS = {ParsecSpec: _gen_parsec, UniformSpec: _gen_uniform,
               HotspotSpec: _gen_hotspot, PermutationSpec: _gen_permutation,
               BurstySpec: _gen_bursty}


def _generate(spec: TrafficSpec, key: jax.Array,
              cfg: NetworkConfig) -> dict:
    gen = _GENERATORS.get(type(spec))
    if gen is None:
        raise TypeError(f"no generator registered for "
                        f"{type(spec).__name__} (known: "
                        f"{sorted(c.__name__ for c in _GENERATORS)})")
    return gen(spec, key, cfg)


@functools.partial(jax.jit, static_argnames=("spec", "cfg"))
def _generate_jit(spec: TrafficSpec, key: jax.Array,
                  cfg: NetworkConfig) -> dict:
    return _generate(spec, key, cfg)


@spans.root("traffic.generate")
def generate(spec, key: jax.Array, cfg: NetworkConfig = NETWORK, *,
             jit: bool = True, dest: bool = False) -> dict:
    """Generate one trace from a spec (or PARSEC app name) and a PRNG key.

    `spec` and `cfg` are static jit arguments — the compiled generator is
    cached per (spec, cfg) and re-keying is compile-free. `jit=False` runs
    the eager path (the property tests pin jit/eager parity).

    `dest=True` attaches the spec's row-stochastic destination matrix
    (`dest` [C, C], see `traffic.dest`) so the simulator resolves actual
    source->destination gateway pressure. Opt-in: traces without `dest`
    ride the uniform-destination path, bit-matching pre-dest numbers. The
    matrix is memoized per (spec, cfg) and attached outside the compiled
    generator, so the jit cache and eager parity are unaffected.
    """
    spec = as_spec(spec)
    arrays = (_generate_jit if jit else _generate)(spec, key, cfg)
    out = dict(arrays, app=spec.name)
    if dest:
        from repro.core.traffic.dest import destination_matrix_jax
        out["dest"] = destination_matrix_jax(spec, cfg)
    return out


def generate_trace(app: str, n_intervals: int, key: jax.Array,
                   cfg: NetworkConfig = NETWORK) -> dict:
    """Generate one PARSEC application trace over `n_intervals` epochs.

    Pre-package API, kept verbatim: sugar for
    ``generate(ParsecSpec(app, n_intervals), key, cfg)``.
    """
    return generate(ParsecSpec(app=app, n_intervals=int(n_intervals)),
                    key, cfg)


def all_app_traces(n_intervals: int, seed: int = 0,
                   cfg: NetworkConfig = NETWORK) -> Dict[str, dict]:
    keys = jax.random.split(jax.random.PRNGKey(seed), len(APP_NAMES))
    return {name: generate_trace(name, n_intervals, k, cfg)
            for name, k in zip(APP_NAMES, keys)}
