"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state, so tests and benches keep their 1-CPU view while
dryrun.py (which sets XLA_FLAGS first) sees 512 placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests (axis sizes 1)."""
    return _make_mesh((1, 1), ("data", "model"))


def make_fleet_mesh(devices=None):
    """1-D ("grid",) mesh over every device in the fleet.

    The DSE mesh: `jax.devices()` spans all processes after
    `repro.core.distributed.init_distributed`, so the sweep axes that the
    rules table maps to "grid" (logical "sweep" / "islands") shard across
    hosts. With one local device this is a size-1 mesh and every sharding
    resolves to a placement no-op — the single-host fallback.
    """
    import numpy as np
    from jax.sharding import Mesh

    devices = list(devices) if devices is not None else list(jax.devices())
    return Mesh(np.asarray(devices), ("grid",))
