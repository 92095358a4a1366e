"""Replica→device placement for the serving fleet.

Training-side scans shard over the data axis of the active mesh
(:mod:`~keystone_tpu.parallel.lanes`); the serving fleet pins whole
replicas the same way: replica ``i`` owns the data-axis device
``i % n_data`` of the active mesh, so a fleet sized "one replica per
device" (the default) keeps every chip busy with independent
micro-batches while the model axis stays available to each replica's
executable. A 1-device environment yields co-resident replicas — still
useful on CPU, where the worker threads overlap host-side work (request
validation, stacking, D2H) with each other's device compute.

Replicas are THREADS of one process and may share a device on any
platform. Cluster workers are PROCESSES: they may share a CPU, but an
accelerator chip belongs to one process at a time, so more worker
processes than chips is refused (:class:`PlacementError`).
"""

from __future__ import annotations

from typing import Any, List, Optional

from .mesh import default_mesh


class PlacementError(ValueError):
    """The placement asked for cannot exist on this platform."""


def data_axis_devices(mesh=None) -> List[Any]:
    """The device owning each data-axis row of the mesh (model index 0 —
    same convention as :func:`~keystone_tpu.parallel.lanes.lane_devices`:
    replica state is data-parallel)."""
    m = mesh if mesh is not None else default_mesh()
    if m.devices.ndim >= 2:
        return list(m.devices[:, 0].flat)
    return list(m.devices.flat)


def worker_device_indices(
    worker_id: int, n_workers: int, mesh=None
) -> List[int]:
    """The data-axis device indices one cluster worker PROCESS owns:
    a balanced contiguous partition of the axis across ``n_workers``
    (worker ``w`` of ``W`` over ``D`` devices owns ``[wD/W, (w+1)D/W)``),
    so the process tier carves the mesh the same way the thread tier
    carves it into replicas. More workers than devices yields
    co-resident workers (``[w % D]``) on the CPU platform only, where
    separate processes still overlap host-side work across GILs; on an
    accelerator it raises :class:`PlacementError`."""
    if not 0 <= worker_id < n_workers:
        raise ValueError(
            f"worker_id {worker_id} outside [0, {n_workers})"
        )
    devs = data_axis_devices(mesh)
    n_dev = len(devs)
    if n_dev < n_workers:
        platform = devs[0].platform
        if platform != "cpu":
            raise PlacementError(
                f"{n_workers} worker processes over {n_dev} {platform} "
                "device(s): a chip belongs to one process at a time — "
                f"use at most {n_dev} worker(s), and replicas (threads) "
                "to share a chip"
            )
        return [worker_id % n_dev]
    lo = worker_id * n_dev // n_workers
    hi = (worker_id + 1) * n_dev // n_workers
    return list(range(lo, hi))


def replica_devices(
    n: Optional[int] = None, mesh=None
) -> List[Any]:
    """Device for each of ``n`` serving replicas, round-robin over the
    data axis of the active mesh. ``n=None`` sizes the fleet at one
    replica per data-axis device — the ISSUE's default shape."""
    devs = data_axis_devices(mesh)
    if n is None:
        n = len(devs)
    if n < 1:
        raise ValueError(f"need at least one replica, got {n}")
    return [devs[i % len(devs)] for i in range(n)]
