"""The Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode (every other kernel test) never meets Mosaic's rules: the
(8, 128) block tiling, integer-only iotas, the ops it can legalize, the
1 MiB of SMEM. These tests compile `epoch_step` and `noc_step` for a v5e
that is described, not attached (`jax.experimental.topologies`), with
`interpret=False`, and assert that the program holds the kernel
(`tpu_custom_call`). Nothing runs, so they say nothing about results or
speed. Each compile takes about a second.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and it
keeps it until it exits.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import simulator as S
from repro.core.simulator import SimConfig
from repro.kernels.epoch_step.ops import epoch_run_pallas
from repro.kernels.noc_step.kernel import noc_run_pallas
from repro.kernels.noc_step.ops import build_topology


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    """A described v5e chip; the persistent compilation cache is off while
    the module's compiles run (such entries cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_kernel_compiles(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _sim(n_chiplets: int) -> SimConfig:
    sim = SimConfig()
    return dataclasses.replace(
        sim, cfg=sim.cfg.with_topology(n_chiplets=n_chiplets))


def _epoch_fn(sim, *, dest: bool, faults: bool):
    tables = {k: np.asarray(v)
              for k, v in S.selection_tables_jax(sim.cfg).items()}

    def run(ext, mem, intra, t_mask, *extra):
        extra = list(extra)
        xs = (ext, mem, intra, jnp.zeros_like(mem), t_mask)
        if faults:
            xs = xs + tuple(extra[:3])
            extra = extra[3:]
        return epoch_run_pallas(S._initial_state(sim), xs, sim, tables,
                                dest=extra[0] if dest else None,
                                faulted=faults, interpret=False)
    return run


# (chiplets, intervals, dest, faults): the Table 1 system clean at
# fig11's 100 intervals (one grid step), the same with a destination
# matrix and fault frames over 300 intervals (three 128-interval steps),
# and 256 chiplets with a destination matrix over three steps.
@pytest.mark.parametrize("c,t,dest,faults", [
    (4, 100, False, False),
    (4, 300, True, True),
    (256, 300, True, False),
])
def test_epoch_step_compiles_for_v5e(one_chip, c, t, dest, faults):
    sim = _sim(c)
    g = sim.cfg.max_gateways_per_chiplet
    args = [_sds((t, c), one_chip), _sds((t,), one_chip),
            _sds((t, c), one_chip), _sds((t,), one_chip)]
    if faults:
        args += [_sds((t, c, g), one_chip), _sds((t, c, g), one_chip),
                 _sds((t,), one_chip)]
    if dest:
        args.append(_sds((c, c), one_chip))
    _assert_kernel_compiles(_epoch_fn(sim, dest=dest, faults=faults), args)


def test_epoch_step_compiles_vmapped_in_sweep(one_chip, monkeypatch):
    """`sweep` vmaps the kernel over a runtime grid: the batched grid and
    block specs must meet the tiling rule too. `resolve_interpret` sees
    the CPU backend here, so the test steers the wrapper to compile."""
    from repro.kernels.epoch_step import ops
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret=None: False)
    sim = dataclasses.replace(SimConfig(), epoch_kernel=True)
    t, c, k = 300, sim.cfg.n_chiplets, 8
    tables = {key: np.asarray(v)
              for key, v in S.selection_tables_jax(sim.cfg).items()}

    def run(ext, mem, intra, t_mask, l_m):
        return S._sweep_jit.__wrapped__(ext, mem, intra, jnp.float32(0.3),
                                        t_mask, tables, {"l_m": l_m},
                                        sim=sim)
    _assert_kernel_compiles(run, [
        _sds((t, c), one_chip), _sds((t,), one_chip), _sds((t, c), one_chip),
        _sds((t,), one_chip), _sds((k,), one_chip)])


# One 256-cycle grid step, and 2048 cycles over eight steps.
@pytest.mark.parametrize("t", [256, 2048])
def test_noc_step_compiles_for_v5e(one_chip, t):
    next_mat, drain, buf, _ = build_topology(4, 4)
    r = next_mat.shape[0]

    def run(arrivals, t_mask):
        return noc_run_pallas(arrivals, jnp.asarray(next_mat),
                              jnp.asarray(drain), jnp.asarray(buf),
                              t_mask=t_mask, interpret=False)
    _assert_kernel_compiles(run, [_sds((t, r), one_chip),
                                  _sds((t,), one_chip)])
