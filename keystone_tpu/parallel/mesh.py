"""Device-mesh and sharding helpers — the substrate that replaces Spark.

The reference distributes work as RDD partitions over executors coordinated by
a driver (SURVEY §2.7); every distributed primitive it uses (mapPartitions,
treeReduce, broadcast, shuffle) has a mesh-native equivalent here:

  * RDD partitioning      -> batch-dim sharding of a ``jax.Array`` over a Mesh
  * ``sc.broadcast``      -> replicated sharding (XLA keeps one copy per device)
  * mlmatrix ``treeReduce``-> ``psum`` over ICI inside a jit program (XLA picks
                             the reduction topology; no tree tuning knob needed)
  * HashPartitioner shuffle-> explicit ``jax.device_put`` resharding on host

Nothing in this module is TPU-only: the same code runs on the CPU backend with
``--xla_force_host_platform_device_count=N`` standing in for a slice, exactly
the way Spark ``local[n]`` stands in for a cluster in the reference tests
(src/test/scala/keystoneml/workflow/PipelineContext.scala:9-25).

Axis conventions (used consistently across the framework):
  * ``"data"``  — batch/example axis (data parallelism; rows of design matrices)
  * ``"model"`` — feature/class axis (model parallelism; column blocks)
"""

from __future__ import annotations

import contextlib
import sys
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"

# Process-wide default mesh (settable, like PipelineEnv's optimizer registry).
_default_mesh: Optional[Mesh] = None


def make_mesh(
    n_data: Optional[int] = None,
    n_model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ``(data, model)`` mesh over ``devices``.

    ``n_data=None`` uses all remaining devices on the data axis. A 1-device
    environment yields a trivial mesh — all code paths still work, XLA just
    compiles away the collectives.
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_data is None:
        n_data = len(devices) // n_model
    use = n_data * n_model
    if use > len(devices) or n_data < 1 or n_model < 1:
        raise ValueError(
            f"mesh {n_data}x{n_model} needs {use} devices, have {len(devices)}"
        )
    dev_array = np.asarray(devices[:use]).reshape(n_data, n_model)
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))


def device_summary() -> dict:
    """The platform this process got, as jax reports it: ``platform``,
    ``kind`` and ``count``. Initializes the backend — so on an
    accelerator only the ONE process that owns the chip calls it."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def report_platform() -> None:
    """One stderr line naming the platform this process runs on, printed
    by the CLI entry points at start — never by a router parent in
    ``--workers`` mode, which must stay off the device."""
    d = device_summary()
    print(
        f"keystone_tpu: platform {d['platform']} ({d['kind']} x{d['count']})",
        file=sys.stderr,
    )


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _default_mesh
    _default_mesh = mesh


def default_mesh() -> Mesh:
    """The process-default mesh (lazily a full data-parallel mesh)."""
    global _default_mesh
    if _default_mesh is None:
        _default_mesh = make_mesh()
    return _default_mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Temporarily set the process-default mesh."""
    global _default_mesh
    prev = _default_mesh
    _default_mesh = mesh
    try:
        yield mesh
    finally:
        _default_mesh = prev


# ---- sharding constructors ------------------------------------------------


def batch_sharding(mesh: Optional[Mesh] = None, ndim: int = 2) -> NamedSharding:
    """Rows sharded over the data axis, all other dims replicated — the layout
    of every RDD-of-vectors in the reference."""
    mesh = mesh or default_mesh()
    spec = P(DATA_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    """Fully replicated — the equivalent of ``sc.broadcast`` of a model."""
    mesh = mesh or default_mesh()
    return NamedSharding(mesh, P())


def column_sharding(mesh: Optional[Mesh] = None, ndim: int = 2) -> NamedSharding:
    """Last dim sharded over the model axis (feature-block parallelism —
    the mesh-native VectorSplitter layout)."""
    mesh = mesh or default_mesh()
    spec = P(*([None] * (ndim - 1)), MODEL_AXIS)
    return NamedSharding(mesh, spec)


# ---- placement helpers ----------------------------------------------------


def shard_batch(x: Any, mesh: Optional[Mesh] = None) -> jax.Array:
    """Place ``x`` in HBM sharded along its leading (batch) dim.

    Sharded placement needs the batch size divisible by the data-axis size;
    otherwise this falls back to replicated placement (always correct —
    XLA reshards inside jit as needed — just not memory-distributed). Callers
    that control their batch size should keep it divisible, or zero-pad via
    ``pad_to_multiple`` when padding is semantically safe (it is for
    Gram/QR-style reductions; it is NOT for means or row counts).
    """
    import jax.numpy as jnp

    x = jnp.asarray(x)
    m = mesh or default_mesh()
    if x.ndim == 0 or x.shape[0] % m.shape[DATA_AXIS] != 0:
        return jax.device_put(x, replicated_sharding(m))
    return jax.device_put(x, batch_sharding(m, x.ndim))


def shard_classes(x: Any, axis: int = 0, mesh: Optional[Mesh] = None) -> jax.Array:
    """Place ``x`` sharded along ``axis`` over the MODEL axis.

    This is the model-parallel layout for per-class work: the weighted
    solver's batched per-class Gram/Cholesky stack (axis 0 = class) shards
    over the model axis so each model-axis device factorizes its own slice
    of classes — the mesh-native analogue of the reference distributing
    per-class solves across executors
    (BlockWeightedLeastSquares.scala:177-313). Falls back to replication
    when the axis length doesn't divide the model-axis size."""
    import jax.numpy as jnp

    x = jnp.asarray(x)
    m = mesh or default_mesh()
    n_model = m.shape[MODEL_AXIS]
    if n_model <= 1:
        return x  # data-only mesh: true no-op, no placement traffic
    if x.ndim == 0 or x.shape[axis] % n_model != 0:
        return jax.device_put(x, replicated_sharding(m))
    spec = [None] * x.ndim
    spec[axis] = MODEL_AXIS
    return jax.device_put(x, NamedSharding(m, P(*spec)))


def replicate(x: Any, mesh: Optional[Mesh] = None) -> jax.Array:
    import jax.numpy as jnp

    x = jnp.asarray(x)
    return jax.device_put(x, replicated_sharding(mesh))


def mesh_n_data(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or default_mesh()
    return mesh.shape[DATA_AXIS]


def pad_to_multiple(x, multiple: int, axis: int = 0) -> Tuple[Any, int]:
    """Zero-pad ``axis`` up to a multiple (for even sharding); returns
    (padded, original_length)."""
    import jax.numpy as jnp

    x = jnp.asarray(x)
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad), n
