"""Distribution substrate: sharding rules, topologies, 2-D mesh fleets,
gradient compression, group-sharded sketch fleets."""

from .sharding import (
    param_shardings,
    batch_shardings,
    dp_axes,
    set_activation_mesh,
    shard_activation,
)
from .topology import (
    DATA_AXIS,
    LANE_AXIS,
    TopologySpec,
)
from .mesh2d import (
    Mesh2DFleet,
    merge_replica_planes,
)
from .group_sharding import (
    GROUP_AXIS,
    ShardedGroupFleet,
    group_mesh,
)

__all__ = [
    "param_shardings",
    "batch_shardings",
    "dp_axes",
    "set_activation_mesh",
    "shard_activation",
    "DATA_AXIS",
    "LANE_AXIS",
    "TopologySpec",
    "Mesh2DFleet",
    "merge_replica_planes",
    "GROUP_AXIS",
    "ShardedGroupFleet",
    "group_mesh",
]
