"""Cold-start elimination tests: persistent compilation cache wiring,
AOT-vs-jit parity per entry point, memoization, warmup, and the
cross-process round-trip (compile in one process, serve the next
process's first dispatch from the serialized executable on disk).
"""
from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import simulator as S
from repro.core import traffic
from repro.core.simulator import Arch, SimConfig
from repro.runtime import cache as rcache

REPO = Path(__file__).resolve().parent.parent


def _sim() -> SimConfig:
    return SimConfig().with_arch(Arch.RESIPI)


def _trace(sim, n=8, seed=0, cfg=None):
    return traffic.generate(traffic.UniformSpec(n_intervals=n),
                            jax.random.PRNGKey(seed), cfg or sim.cfg)


def _assert_tree_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture
def cache_tmp(tmp_path):
    """Point the persistent cache at a throwaway dir, restore after."""
    prev = rcache.cache_dir()
    rcache.clear_aot_cache()    # earlier tests' memos would skip persisting
    try:
        yield rcache.enable_persistent_cache(tmp_path / "jax-cache")
    finally:
        rcache._CACHE["dir"] = prev
        jax.config.update("jax_compilation_cache_dir",
                          str(prev) if prev is not None else None)
        rcache.clear_aot_cache()


# ---------------------------------------------------------------------------
# Persistent cache wiring
# ---------------------------------------------------------------------------

def test_enable_persistent_cache_creates_dir_and_reports(cache_tmp):
    assert cache_tmp.is_dir()
    assert rcache.cache_dir() == cache_tmp
    stats = rcache.persistent_cache_stats()
    assert stats["enabled"] and stats["dir"] == str(cache_tmp)


def test_persistent_cache_stats_disabled_default(tmp_path):
    stats = rcache.persistent_cache_stats(tmp_path / "nope")
    assert stats["entries"] == 0 and stats["bytes"] == 0


# ---------------------------------------------------------------------------
# AOT-vs-jit parity (the AotEntry contract: same inputs, same bits)
# ---------------------------------------------------------------------------

def test_aot_simulate_matches_jit():
    sim = _sim()
    tr = _trace(sim)
    exe = rcache.aot_compile("simulate", tr, sim)
    _assert_tree_equal(exe(tr, sim)["summary"],
                       S.simulate(tr, sim)["summary"])


def test_aot_sweep_matches_jit():
    sim = _sim()
    tr = _trace(sim)
    exe = rcache.aot_compile("sweep", tr, sim, l_m=[0.01, 0.02])
    _assert_tree_equal(exe(tr, sim, l_m=[0.01, 0.02])["summary"],
                       S.sweep(tr, sim, l_m=[0.01, 0.02])["summary"])


def test_aot_sweep_topology_matches_jit():
    sim = _sim()
    tr = _trace(sim, cfg=sim.cfg.with_topology(n_chiplets=9))
    exe = rcache.aot_compile("sweep_topology", tr, sim, n_chiplets=[4, 9])
    _assert_tree_equal(exe(tr, sim, n_chiplets=[4, 9])["summary"],
                       S.sweep_topology(tr, sim, n_chiplets=[4, 9])["summary"])


def test_aot_session_tick_matches_jit():
    sim = _sim()
    tr = _trace(sim)
    states = S.init_session_states(sim, 1)
    ext = np.asarray(tr["ext_load"], np.float32)[None]
    batch = {"ext_load": ext,
             "mem_load": np.asarray(tr["mem_load"], np.float32)[None],
             "int_load": np.asarray(tr["int_load"], np.float32)[None],
             "ext_frac": np.asarray([tr["ext_frac"]], np.float32),
             "t_mask": np.ones(ext.shape[:2], np.float32)}
    tables = S.selection_tables_jax(sim.cfg)
    exe = rcache.aot_compile("session_tick", states, batch, tables, sim)
    _assert_tree_equal(exe(states, batch, tables, sim),
                       S.session_tick(states, batch, tables, sim))


@pytest.mark.parametrize("entry", ["simulate", "session_tick"])
def test_aot_faulted_entry_matches_jit(entry):
    """The AOT operands are the entry point's own, fault frame included:
    the compiled call runs the faulted program, not the clean one."""
    from repro.core import faults

    sim = _sim()
    tr = _trace(sim)
    frame = faults.compile_faults(
        [faults.GatewayFault(start=2, chiplet=0, slot=0),
         faults.LossDrift(start=3, db_per_interval=0.5)], sim.cfg, 8)
    if entry == "simulate":
        args, kw = (faults.attach_faults(tr, frame), sim), {}
    else:
        args = (S.init_session_states(sim, 2),
                S.stack_traces([tr, tr], pad=True),
                S.selection_tables_jax(sim.cfg), sim)
        kw = {"frame": frame}
    got = rcache.aot_compile(entry, *args, **kw)(*args, **kw)
    _assert_tree_equal(got, getattr(S, entry)(*args, **kw))
    # The frame bites: the clean program reads another power.
    if entry == "simulate":
        clean = S.simulate(tr, sim)
        assert float(got["summary"]["mean_power_mw"]) \
            != float(clean["summary"]["mean_power_mw"])
    else:
        clean = S.session_tick(*args)
        assert not np.array_equal(np.asarray(got[2]["power_mw"]),
                                  np.asarray(clean[2]["power_mw"]))


def test_aot_search_matches_jit():
    from repro.core import pareto

    sim = _sim()
    tr = _trace(sim, cfg=sim.cfg.with_topology(n_chiplets=9))
    kw = dict(n_chiplets=[4, 9], islands=2, generations=2, population=2,
              archive=8, seed=5)
    exe = rcache.aot_compile("search", tr, sim, **kw)
    built, statics, _ = pareto._codesign_operands(tr, sim, **kw)
    _assert_tree_equal(exe(tr, sim, **kw),
                       pareto._codesign_jit(*built, **statics))
    assert exe is rcache.aot_compile("search", tr, sim, **kw)  # memo hit
    assert "search" in rcache.AOT_ENTRY_POINTS


def test_warmup_search_entry_runs():
    sim = _sim()
    walls = rcache.warmup(sim, n_intervals=4, entries=("search",),
                          grids={"n_chiplets": [sim.cfg.n_chiplets]})
    assert walls["search"] > 0.0


def test_aot_memoizes_on_config_and_shapes():
    sim = _sim()
    tr = _trace(sim)
    a = rcache.aot_compile("simulate", tr, sim)
    b = rcache.aot_compile("simulate", _trace(sim, seed=3), sim)
    assert a is b                       # same shapes: cached handle
    c = rcache.aot_compile("simulate", _trace(sim, n=12), sim)
    assert c is not a                   # new trace length: new executable
    assert rcache.aot_cache_stats()["by_entry"]["simulate"] >= 2


def test_aot_unknown_entry_raises():
    with pytest.raises(ValueError, match="unknown AOT entry"):
        rcache.aot_compile("nope", None, _sim())


# ---------------------------------------------------------------------------
# Warmup
# ---------------------------------------------------------------------------

def test_warmup_runs_every_entry_point():
    sim = _sim()
    walls = rcache.warmup(
        sim, n_intervals=8,
        entries=("simulate", "sweep", "sweep_topology", "session_tick"))
    assert set(walls) == {"simulate", "sweep", "sweep_topology",
                          "session_tick"}
    assert all(w > 0.0 for w in walls.values())


def test_warmup_unknown_entry_raises():
    with pytest.raises(ValueError, match="unknown warmup entry"):
        rcache.warmup(_sim(), entries=("bogus",))


# ---------------------------------------------------------------------------
# Serialized-executable round-trips
# ---------------------------------------------------------------------------

def test_aot_serialized_roundtrip_in_process(cache_tmp, caplog):
    sim = _sim()
    tr = _trace(sim)
    caplog.set_level(logging.INFO, logger="repro.runtime.cache")
    ref = rcache.aot_compile("simulate", tr, sim)(tr, sim)
    files = list((cache_tmp / "aot").glob("*.bin"))
    assert len(files) == 1 and files[0].name.startswith("simulate-")
    rcache.clear_aot_cache()            # drop the memo, keep the disk blob
    caplog.clear()
    out = rcache.aot_compile("simulate", tr, sim)(tr, sim)
    assert any("AOT-loaded" in r.message for r in caplog.records)
    _assert_tree_equal(out["summary"], ref["summary"])


def test_stale_aot_blob_falls_back_to_recompile(cache_tmp, caplog):
    sim = _sim()
    tr = _trace(sim)
    caplog.set_level(logging.INFO, logger="repro.runtime.cache")
    rcache.aot_compile("simulate", tr, sim)
    (path,) = (cache_tmp / "aot").glob("*.bin")
    path.write_bytes(b"not a serialized executable")
    rcache.clear_aot_cache()
    out = rcache.aot_compile("simulate", tr, sim)(tr, sim)
    assert any("recompiling" in r.message for r in caplog.records)
    _assert_tree_equal(out["summary"], S.simulate(tr, sim)["summary"])


_CHILD = r"""
import json, logging, pathlib, sys
import jax, numpy as np
from repro.core import traffic
from repro.core import simulator as S
from repro.core.simulator import Arch, SimConfig
from repro.runtime import cache as rcache

msgs = []
h = logging.Handler()
h.emit = lambda rec: msgs.append(rec.getMessage())
logging.getLogger("repro.runtime.cache").addHandler(h)
logging.getLogger("repro.runtime.cache").setLevel(logging.INFO)

cache_dir = pathlib.Path(sys.argv[1])
rcache.enable_persistent_cache(cache_dir)
sim = SimConfig().with_arch(Arch.RESIPI)
tr = traffic.generate(traffic.UniformSpec(n_intervals=8),
                      jax.random.PRNGKey(0), sim.cfg)
exe = rcache.aot_compile("simulate", tr, sim)
out = exe(tr, sim)
print("RESULT " + json.dumps({
    "mean_latency": float(out["summary"]["mean_latency"]),
    "loaded": any(m.startswith("AOT-loaded") for m in msgs),
    "aot_files": len(list((cache_dir / "aot").glob("*.bin")))}))
"""


def _run_child(cache_dir):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", _CHILD, str(cache_dir)],
                          cwd=REPO, env=env, timeout=600,
                          capture_output=True, text=True)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_persistent_cache_cross_process_roundtrip(tmp_path):
    # Process 1 compiles + persists; process 2's first aot_compile serves
    # the serialized executable from disk (no tracing, no XLA) and
    # bit-matches. This is the fleet workers' warm-start contract.
    cache = tmp_path / "shared-cache"
    first = _run_child(cache)
    assert not first["loaded"] and first["aot_files"] == 1
    second = _run_child(cache)
    assert second["loaded"] and second["aot_files"] == 1
    assert second["mean_latency"] == first["mean_latency"]
