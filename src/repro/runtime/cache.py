"""Cold-start elimination: persistent compilation cache + AOT entry points.

Every fresh process pays ~0.6-2.3 s of XLA compiles per engine entry point
before its first sweep returns. This module removes that wall twice over:

  * `enable_persistent_cache` wires jax's persistent compilation cache
    (`jax_compilation_cache_dir`) to a shared directory, with thresholds
    opened up so every engine executable is cached — a fleet worker whose
    sibling (or yesterday's run) compiled the same (config, shape) serves
    its first dispatch from disk instead of XLA;
  * `aot_compile` lowers a hot entry point ahead of time
    (`jax.jit(...).lower(...).compile()`) and memoizes the compiled
    executable keyed on (entry, config, input shapes/dtypes), so serving
    paths can pin an executable explicitly and tests can assert
    AOT-vs-jit parity. With the persistent cache enabled the compiled
    executable is ALSO serialized to disk
    (`jax.experimental.serialize_executable`), so a later process's
    `aot_compile` skips tracing entirely — the jit-level persistent cache
    removes XLA compile time but still re-traces; the serialized
    executable removes both;
  * `warmup` runs selected public entry points once on representative
    inputs (blocking), which both fills the in-process jit caches and
    populates the persistent cache for every process that follows.

All helpers are single-host no-risk: nothing here changes numerics (the
cache is keyed on the exact HLO) and everything degrades to plain jit.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pathlib
import pickle
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

log = logging.getLogger("repro.runtime.cache")

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# The fallback lives inside the checkout (listed in .gitignore): the path is
# part of the cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_CACHE = {"dir": None}
_AOT: Dict[tuple, "AotEntry"] = {}

#: Entry points `aot_compile` / `warmup` know how to lower.
AOT_ENTRY_POINTS = ("simulate", "sweep", "sweep_topology", "session_tick",
                    "search")


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------

def enable_persistent_cache(cache_dir: Optional[str] = None) -> pathlib.Path:
    """Turn on jax's persistent compilation cache (directory created if
    missing).

    The directory is `$JAX_COMPILATION_CACHE_DIR` when that is set, else
    `DEFAULT_CACHE_DIR` (`.jax_cache` at the root of the checkout).
    `cache_dir` overrides both; only tests pass it, to isolate a cache.
    The min-compile-time and min-entry-size thresholds are opened up so
    every engine executable lands in the cache — the whole point is
    eliminating sub-second cold compiles, which the defaults skip.
    Idempotent; returns the resolved directory.
    """
    if cache_dir is None:
        cache_dir = os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR
    path = pathlib.Path(cache_dir).expanduser()
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _CACHE["dir"] = path
    log.info("persistent compilation cache at %s", path)
    return path


def cache_dir() -> Optional[pathlib.Path]:
    """The enabled cache directory (None before enable_persistent_cache)."""
    return _CACHE["dir"]


def persistent_cache_stats(path=None) -> dict:
    """Entry count + total bytes of the persistent cache directory
    (includes the serialized AOT executables under its aot/ subdir)."""
    path = pathlib.Path(path).expanduser() if path is not None \
        else _CACHE["dir"]
    if path is None or not pathlib.Path(path).is_dir():
        return {"enabled": _CACHE["dir"] is not None, "dir": None,
                "entries": 0, "bytes": 0}
    files = [f for f in pathlib.Path(path).rglob("*") if f.is_file()]
    return {"enabled": _CACHE["dir"] is not None, "dir": str(path),
            "entries": len(files),
            "bytes": int(sum(f.stat().st_size for f in files))}


# ---------------------------------------------------------------------------
# AOT lowering of hot entry points
# ---------------------------------------------------------------------------

class AotEntry:
    """One AOT-compiled engine entry point.

    Calling it rebuilds the device arrays exactly like the public entry
    point and launches the pre-compiled executable — same inputs, same
    results (parity pinned by tests/test_runtime_cache.py), zero compile
    on the call path.
    """

    def __init__(self, entry: str, key: tuple, compiled,
                 build: Callable[..., tuple]):
        self.entry = entry
        self.key = key
        self.compiled = compiled
        self._build = build

    def __call__(self, *args, **kw):
        return self.compiled(*self._build(*args, **kw))

    def __repr__(self):
        return f"AotEntry({self.entry}, shapes={self.key[-1]})"


def _shape_key(args) -> tuple:
    return tuple(
        (tuple(np.shape(leaf)), str(np.asarray(leaf).dtype))
        for leaf in jax.tree.leaves(args))


def _grid_key(grids: dict) -> tuple:
    out = []
    for name in sorted(grids):
        v = grids[name]
        if name == "gateway_positions":
            out.append((name, tuple(None if p is None else tuple(map(tuple, p))
                                    for p in v)))
        else:
            out.append((name, tuple(np.asarray(v).reshape(-1).tolist())))
    return tuple(out)


def _param_key(kw: dict) -> tuple:
    """Hashable memo key for the "search" entry's mixed kwargs (ints,
    floats, grid lists, the nested knob_grids dict)."""
    def leaf(v):
        if v is None:
            return None
        if isinstance(v, dict):
            return tuple((k, leaf(v[k])) for k in sorted(v))
        if np.ndim(v) > 0:
            return tuple(np.asarray(v).reshape(-1).tolist())
        return v
    return tuple((name, leaf(kw[name])) for name in sorted(kw))


def _builders():
    """entry name -> (operands, jit_fn). `operands(*args, **kw)` takes the
    public entry point's arguments and returns (jit operands, static jit
    kwargs) from the simulator's own operand function, so the compiled call
    is fed exactly what the public entry point feeds its jit."""
    from repro.core import pareto, simulator as S

    def sweep_topology(trace, sim, **grids):
        built, sim_p = S._sweep_topology_operands(trace, sim, grids)
        return built, {"sim": sim_p}

    return {
        "simulate": (lambda trace, sim: (S._simulate_operands(trace, sim),
                                         {"sim": sim}), S._simulate_jit),
        "sweep": (lambda trace, sim, **fields: (
            S._sweep_operands(trace, sim, fields), {"sim": sim}),
            S._sweep_jit),
        "sweep_topology": (sweep_topology, S._sweep_topology_jit),
        "session_tick": (lambda states, batch, tables, sim, frame=None: (
            S._session_tick_operands(states, batch, tables, frame),
            {"sim": sim}), S._session_tick_jit),
        "search": (lambda trace, sim, **kw: pareto._codesign_operands(
            trace, sim, **kw)[:2], pareto._codesign_jit)}


def _persist_path(key: tuple) -> Optional[pathlib.Path]:
    """Disk slot for a serialized AOT executable (None when the persistent
    cache is off). Keyed on the same (entry, config, grids, shapes) tuple
    as the in-process memo — `repr` of frozen dataclasses is stable."""
    d = _CACHE["dir"]
    if d is None:
        return None
    digest = hashlib.sha256(repr(key).encode()).hexdigest()[:32]
    return pathlib.Path(d) / "aot" / f"{key[0]}-{digest}.bin"


def _load_persisted(path: pathlib.Path):
    from jax.experimental import serialize_executable

    blob, in_tree, out_tree = pickle.loads(path.read_bytes())
    return serialize_executable.deserialize_and_load(blob, in_tree, out_tree)


def _persist(path: pathlib.Path, compiled) -> None:
    from jax.experimental import serialize_executable

    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(pickle.dumps(serialize_executable.serialize(compiled)))


def aot_compile(entry: str, *args, **kw) -> AotEntry:
    """AOT-lower one engine entry point for these exact (config, shapes).

    ::

        exe = aot_compile("simulate", trace, sim)
        out = exe(trace, sim)            # no compile, jit-parity results

    Entries: "simulate" (trace, sim), "sweep" (trace, sim, **fields),
    "sweep_topology" (trace, sim, **grids), "session_tick" (states, batch,
    tables, sim[, frame]), "search" (trace, sim, **search_codesign kwargs —
    the Pareto co-design dispatch, so a fleet worker's first
    `search_codesign` skips tracing + XLA). Compiled executables are
    memoized on (entry, sim config, grid values, input shapes/dtypes) — a
    second call with a same-shaped trace returns the cached handle. Compiles go through the persistent
    cache when `enable_persistent_cache` is on, so AOT warmup in one
    process is compile-free in the next.
    """
    builders = _builders()
    if entry not in builders:
        raise ValueError(f"unknown AOT entry point {entry!r} "
                         f"(choose from {AOT_ENTRY_POINTS})")
    build, jit_fn = builders[entry]
    built, lower_kw = build(*args, **kw)
    if entry == "session_tick":  # a fault frame is an operand, not a key
        sim, params = args[3], ()
    else:
        sim = args[1]
        params = _param_key(kw) if entry == "search" else _grid_key(kw)
    key = (entry, sim, params, _shape_key(built))
    rebuild = lambda *a, **k: build(*a, **k)[0]

    hit = _AOT.get(key)
    if hit is not None:
        return hit
    path = _persist_path(key)
    if path is not None and path.exists():
        try:  # serialized executable: no tracing, no XLA — the warm path
            t0 = time.perf_counter()
            compiled = _load_persisted(path)
            log.info("AOT-loaded %s from %s in %.3fs", entry, path.name,
                     time.perf_counter() - t0)
            exe = AotEntry(entry, key, compiled, rebuild)
            _AOT[key] = exe
            return exe
        except Exception as e:  # stale/foreign blob: recompile below
            log.warning("could not load persisted AOT %s (%r); recompiling",
                        path.name, e)
    t0 = time.perf_counter()
    compiled = jit_fn.lower(*built, **lower_kw).compile()
    log.info("AOT-compiled %s in %.3fs (key shapes: %d operands)",
             entry, time.perf_counter() - t0, len(jax.tree.leaves(built)))
    if path is not None:
        try:
            _persist(path, compiled)
        except Exception as e:  # pragma: no cover - serialization support
            log.warning("could not persist AOT %s (%r)", entry, e)
    exe = AotEntry(entry, key, compiled, rebuild)
    _AOT[key] = exe
    return exe


def aot_cache_stats() -> dict:
    """Per-entry count of memoized AOT executables."""
    out: Dict[str, int] = {}
    for key in _AOT:
        out[key[0]] = out.get(key[0], 0) + 1
    return {"entries": len(_AOT), "by_entry": out}


def clear_aot_cache() -> None:
    _AOT.clear()


# ---------------------------------------------------------------------------
# Warmup
# ---------------------------------------------------------------------------

def warmup(sim, *, trace: Optional[dict] = None, n_intervals: int = 16,
           entries: Tuple[str, ...] = ("simulate", "sweep_topology"),
           grids: Optional[dict] = None, seed: int = 0) -> dict:
    """Run public entry points once, blocking: fills this process's jit
    caches AND the persistent cache for every process that follows.

    Pass the `trace` (and `grids` for "sweep_topology"/"sweep") your real
    workload will use — compilation caches key on exact shapes, so warming
    with representative shapes is what makes the real first dispatch free.
    Returns {entry: seconds} wall times (compile-inclusive).
    """
    from repro.core import simulator as S
    from repro.core import traffic

    if trace is None:
        trace = traffic.generate(
            traffic.UniformSpec(n_intervals=n_intervals),
            jax.random.PRNGKey(seed), sim.cfg)
    walls = {}
    for entry in entries:
        t0 = time.perf_counter()
        if entry == "simulate":
            out = S.simulate(trace, sim)
        elif entry == "sweep":
            fields = grids or {"l_m": [0.01]}
            out = S.sweep(trace, sim, **fields)
        elif entry == "sweep_topology":
            g = grids or {"n_chiplets": [sim.cfg.n_chiplets]}
            out = S.sweep_topology(trace, sim, **g)
        elif entry == "session_tick":
            states = S.init_session_states(sim, 1)
            batch = {k: np.asarray(trace[k], np.float32)[None] for k in
                     ("ext_load", "mem_load", "int_load", "ext_frac")}
            batch["t_mask"] = np.ones(batch["mem_load"].shape, np.float32)
            out = S.session_tick(states, batch,
                                 S.selection_tables_jax(sim.cfg), sim)
        elif entry == "search":
            from repro.core import pareto

            g = grids or {"n_chiplets": [sim.cfg.n_chiplets]}
            out = pareto.search_codesign(trace, sim, islands=2,
                                         generations=2, population=2, **g)
            out = out["island_scores"]
        else:
            raise ValueError(f"unknown warmup entry {entry!r} "
                             f"(choose from {AOT_ENTRY_POINTS})")
        jax.block_until_ready(out)
        walls[entry] = time.perf_counter() - t0
    log.info("warmup: %s", {k: f"{v:.3f}s" for k, v in walls.items()})
    return walls
