"""Worker-process placement: co-residency is a CPU notion; an accelerator
chip belongs to one process at a time."""

from types import SimpleNamespace

import numpy as np
import pytest

from keystone_tpu.parallel.placement import (
    PlacementError,
    worker_device_indices,
)


def _mesh(platform, n):
    devices = np.empty(n, dtype=object)
    for i in range(n):
        devices[i] = SimpleNamespace(platform=platform, id=i)
    return SimpleNamespace(devices=devices)


def test_more_workers_than_chips_is_refused_off_the_cpu():
    with pytest.raises(PlacementError, match="2 worker processes over 1 tpu"):
        worker_device_indices(0, 2, mesh=_mesh("tpu", 1))
    with pytest.raises(PlacementError):
        worker_device_indices(4, 5, mesh=_mesh("tpu", 4))


def test_workers_partition_the_chips_when_they_fit():
    assert worker_device_indices(0, 1, mesh=_mesh("tpu", 1)) == [0]
    assert worker_device_indices(1, 2, mesh=_mesh("tpu", 4)) == [2, 3]


def test_cpu_workers_may_share_a_device():
    assert worker_device_indices(0, 2, mesh=_mesh("cpu", 1)) == [0]
    assert worker_device_indices(2, 3, mesh=_mesh("cpu", 2)) == [0]
