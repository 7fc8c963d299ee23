"""Multi-process (fleet) execution layer for the DSE sweeps.

One process per host, every process running the SAME program: this module
brings up `jax.distributed`, builds a 1-D "grid" mesh over every device in
the fleet, and shards the leading grid axis of a sweep's inputs across it
(NamedSharding / GSPMD), so `shard_sweep`, `sweep_workload`, and
`search_placement_islands` partition their vmapped lanes over hosts with
the same executable they run on one device. Three rules keep it honest:

  * single-host fallback everywhere — with one process and one device every
    helper is a passthrough, so the engine's behaviour (and every existing
    test) is unchanged;
  * all processes construct identical host-side grids (deterministic from
    the seed), so sharding is a pure data-placement decision: each process
    materializes only the rows its devices own (`make_array_from_callback`)
    and closed-over arrays are replicated explicitly;
  * no silent padding — the grid is padded to a device-count multiple by
    repeating the last point, and the pad count is logged and surfaced in
    the sweep's returned summary (`GridSharding.describe`).

The logical->mesh axis mapping rides the MaxText-style rules table
(`repro.sharding.rules`): the DSE axes "sweep" and "islands" both resolve
to the fleet mesh's "grid" axis.
"""
from __future__ import annotations

import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.rules import Rules

log = logging.getLogger("repro.distributed")

# Environment contract between the fleet launcher and its workers
# (repro.launch.fleet sets these before spawning each worker process).
ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

_STATE = {"initialized": False, "info": None}


def init_distributed(*, coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     collectives: str = "gloo") -> dict:
    """Join (or skip) the fleet: `jax.distributed.initialize` from explicit
    args or the REPRO_* environment, with a single-process no-op fallback.

    MUST run before anything touches the jax backend (device queries,
    any jit) — both the coordinator handshake and the CPU collectives
    implementation bind at backend initialization. `collectives` selects
    the CPU cross-process collective transport ("gloo" is the portable
    default); non-CPU backends ignore it. Idempotent: the second call
    returns the first call's info.
    """
    if _STATE["initialized"]:
        return dict(_STATE["info"])
    env = os.environ
    coordinator = coordinator or env.get(ENV_COORDINATOR)
    if num_processes is None:
        num_processes = int(env.get(ENV_NUM_PROCESSES, "1"))
    if process_id is None:
        process_id = int(env.get(ENV_PROCESS_ID, "0"))
    if num_processes <= 1 or coordinator is None:
        info = {"distributed": False, "coordinator": None,
                "num_processes": 1, "process_id": 0}
        _STATE.update(initialized=True, info=info)
        return dict(info)
    if not 0 <= process_id < num_processes:
        raise ValueError(f"process_id {process_id} out of range for "
                         f"{num_processes} processes")
    if collectives:  # must land before the CPU client exists
        jax.config.update("jax_cpu_collectives_implementation", collectives)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    info = {"distributed": True, "coordinator": coordinator,
            "num_processes": num_processes, "process_id": process_id}
    _STATE.update(initialized=True, info=info)
    log.info("joined fleet: process %d/%d via %s (%d global devices)",
             process_id, num_processes, coordinator, len(jax.devices()))
    return dict(info)


def shutdown_distributed() -> None:
    """Leave the fleet (tests / clean worker exit); no-op if never joined."""
    if _STATE["initialized"] and _STATE["info"]["distributed"]:
        jax.distributed.shutdown()
    _STATE.update(initialized=False, info=None)


def is_distributed() -> bool:
    """More than one process in this jax runtime?"""
    return jax.process_count() > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def partition_bounds(grid_points: int, num_shards: int, shard: int):
    """Contiguous [start, stop) of grid shard `shard` of `num_shards`.

    Exactly the block partition a 1-D NamedSharding lays over the padded
    grid axis (pad rows land in the last block and are sliced off), so an
    emulated-host worker computing `grid[start:stop]` reproduces the rows
    a real fleet member owns. The shards are disjoint and cover the grid.
    """
    if not 0 <= shard < num_shards:
        raise ValueError(f"shard {shard} out of range for {num_shards}")
    padded = grid_points + ((-grid_points) % num_shards)
    block = padded // num_shards
    start = min(shard * block, grid_points)
    stop = min(start + block, grid_points)
    return start, stop


class GridSharding:
    """Pad + place a sweep's leading grid axis over the fleet mesh.

    ::

        gs = GridSharding(k)                  # all global devices
        topo = gs.shard(topo)                 # leading axis -> "grid"
        ext = gs.replicate(ext)               # closed-over trace arrays
        out = fn(...)                         # same jitted entry point
        out = gs.gather(out)                  # full results on every host

    Single-device meshes degrade to passthroughs (`replicate` is identity
    when every device is process-local, preserving the single-host
    executables bit-for-bit); multi-process placement materializes only
    the locally-addressable rows per host. The grid is padded to a
    device-count multiple by repeating the last point; `gather` slices the
    pad back off and `describe()` reports it (no silent caps).
    """

    def __init__(self, grid_points: int, *, devices=None,
                 logical_axis: str = "sweep", mesh_axis: str = "grid"):
        self.devices = list(devices) if devices is not None \
            else list(jax.devices())
        if not self.devices:
            raise ValueError("GridSharding needs at least one device")
        self.grid_points = int(grid_points)
        self.n_devices = len(self.devices)
        self.pad = (-self.grid_points) % self.n_devices
        self.mesh = Mesh(np.asarray(self.devices), (mesh_axis,))
        self.rules = Rules(self.mesh, {logical_axis: (mesh_axis,)})
        self.sharding = self.rules.sharding(logical_axis)
        self.replicated = NamedSharding(self.mesh, P())
        self.processes = len({d.process_index for d in self.devices})
        self.multiprocess = self.processes > 1
        self._gather_jit = None
        if self.pad:
            log.info(
                "grid sharding: %d grid points padded with %d repeated "
                "lanes to fill %d devices (%d processes)", self.grid_points,
                self.pad, self.n_devices, self.processes)

    def describe(self) -> dict:
        """Sharding metadata surfaced in sweep summaries (no silent pads)."""
        return {"grid_points": self.grid_points, "pad_lanes": self.pad,
                "devices": self.n_devices, "processes": self.processes}

    # ---------------------------------------------------------- placement
    def pad_tree(self, tree):
        """Repeat each leaf's last grid row `pad` times (sliced off by
        `gather`; repeated points cost compute, never correctness)."""
        if not self.pad:
            return tree

        def _pad(a):
            a = jnp.asarray(a)
            return jnp.concatenate(
                [a, jnp.repeat(a[-1:], self.pad, axis=0)], axis=0)
        return jax.tree.map(_pad, tree)

    def _put(self, a, sharding):
        if not self.multiprocess:
            return jax.device_put(a, sharding)
        # Every process holds the identical host-side grid; each
        # materializes exactly the rows its devices own.
        arr = np.asarray(a)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    def shard(self, tree):
        """Pad the leading axis and place it over the mesh's grid axis."""
        tree = self.pad_tree(tree)
        return jax.tree.map(lambda a: self._put(a, self.sharding), tree)

    def replicate(self, tree):
        """Make closed-over arrays fleet-global (fully replicated).

        Identity on single-process meshes — the engine's existing arrays
        already live where the executable runs, and re-placing them would
        perturb the warm-cache behaviour the tests pin.
        """
        if not self.multiprocess:
            return tree
        return jax.tree.map(
            lambda a: a if a is None else self._put(a, self.replicated),
            tree, is_leaf=lambda x: x is None)

    # ------------------------------------------------------------ results
    def gather(self, tree, *, axis: int = 0):
        """Full (unpadded) results, addressable on every process.

        Multi-process: an all-gather via a jit identity with replicated
        output sharding (each host then holds every shard). The pad rows
        are sliced off along `axis` (axis 1 for [N, K] batched sweeps).
        """
        if self.multiprocess:
            if self._gather_jit is None:
                self._gather_jit = jax.jit(
                    lambda t: t, out_shardings=self.replicated)
            tree = self._gather_jit(tree)
        if self.pad:
            k = self.grid_points
            sl = (slice(None),) * axis + (slice(0, k),)
            tree = jax.tree.map(lambda a: a[sl], tree)
        return tree
