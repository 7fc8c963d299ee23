"""The least work of the interval model at a cell's shapes.

Counted from the model, not from either implementation, so the scan body
and the fused `epoch_step` kernel are judged on the same work:

  * bytes read: each trace once (ext and intra loads [T, C], memory load
    and validity mask [T], float32);
  * bytes written: each lane's per-interval records (latency, power,
    laser power, energy, reconfiguration energy, mean inter-chiplet
    latency in float32, the saturation flag in one byte, and gateways,
    wavelengths and gateway load per real chiplet in four bytes) and its
    eight float32 summaries;
  * operations: per real chiplet and interval the NoC queueing terms,
    controller update and power sums (OPS_PER_CHIPLET), per gateway slot
    the PCM coupling-ratio schedule (OPS_PER_SLOT), per lane and interval
    the scalar reductions (OPS_PER_LANE). Padded chiplets are not work.

The least time is the larger of operations over the peak rate and bytes
over the memory bandwidth. The model's operations are float32 vector
work, for which no peak is published; the bf16 matrix peak stands in, so
the operation bound is an underestimate and the bytes bound is the one
that applies.
"""
from __future__ import annotations

from typing import Sequence

F32 = 4
RECORD_SCALARS = 6          # latency, power, laser, energy, reconfig, mean inter
RECORD_PER_CHIPLET = 3      # g, wavelengths, gateway load
SUMMARY_KEYS = 8
OPS_PER_CHIPLET = 60
OPS_PER_SLOT = 8
OPS_PER_LANE = 50


def interval_work(*, traces: int, trace_chiplets: int, intervals: int,
                  chiplets_per_lane: Sequence[int], gateways: int,
                  memory_gateways: int) -> dict:
    """Bytes and operations of one call."""
    lanes = len(chiplets_per_lane)
    real = sum(chiplets_per_lane)
    read = traces * intervals * (2 * trace_chiplets + 2) * F32
    written = (lanes * intervals * (RECORD_SCALARS * F32 + 1)
               + real * intervals * RECORD_PER_CHIPLET * F32
               + lanes * SUMMARY_KEYS * F32)
    slots = sum(c * gateways + memory_gateways for c in chiplets_per_lane)
    ops = intervals * (real * OPS_PER_CHIPLET + slots * OPS_PER_SLOT
                       + lanes * OPS_PER_LANE)
    return {"bytes": read + written, "ops": ops}


def least_seconds(work: dict, peaks: dict, devices: int = 1) -> dict:
    """The least time on `devices` chips, and which bound sets it."""
    t_ops = work["ops"] / (peaks["bf16_flops_per_s"] * devices)
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * devices)
    return {"seconds": max(t_ops, t_bytes),
            "bound": "bytes" if t_bytes >= t_ops else "ops"}
