"""Device-mesh substrate: mesh construction, sharding helpers, resharding.
Replaces Spark's executor/partition/broadcast/treeReduce machinery (SURVEY
SS2.7) with jax.sharding over ICI/DCN."""

from .lanes import (
    gather_lane_partials,
    lane_devices,
    record_scan_collectives,
    reduce_lane_partials,
    scan_lanes,
)
from .placement import data_axis_devices, replica_devices
from .mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    batch_sharding,
    column_sharding,
    default_mesh,
    device_summary,
    make_mesh,
    mesh_n_data,
    pad_to_multiple,
    replicate,
    replicated_sharding,
    set_default_mesh,
    shard_batch,
    use_mesh,
)

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "batch_sharding",
    "column_sharding",
    "data_axis_devices",
    "default_mesh",
    "device_summary",
    "gather_lane_partials",
    "lane_devices",
    "make_mesh",
    "mesh_n_data",
    "pad_to_multiple",
    "record_scan_collectives",
    "reduce_lane_partials",
    "replica_devices",
    "replicate",
    "replicated_sharding",
    "scan_lanes",
    "set_default_mesh",
    "shard_batch",
    "use_mesh",
]
