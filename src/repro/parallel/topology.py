"""TopologySpec — the declarative (data × lane) placement surface.

Placement used to be spelled as `backend="sharded"` plus a raw `mesh=`
object on FleetSpec: a string × device-mesh pairing that could only name a
1-D lane mesh on one host. TopologySpec replaces both spellings with one
declarative description of WHERE lanes live:

    TopologySpec()                      # single-device (the default)
    TopologySpec(lanes=8)               # 1-D lane mesh over 8 devices
    TopologySpec(data=2, lanes=4)       # 2-D (data × lane) mesh: 2 stream
                                        # replicas × 4 lane shards
    TopologySpec(data=4, devices=devs)  # explicit device list (multi-host:
                                        # jax.distributed global devices)

Axes:
  * `lanes` — how many shards the flattened (G × Q) lane axis splits into.
    Lane shards are embarrassingly parallel (the paper's GROUPBY setting):
    zero collectives during ingest, exactly the PR-2 1-D mesh.
  * `data`  — how many stream REPLICAS ingest disjoint chunk shards of the
    same lane fleet. Replicas merge through the pinned deterministic rule
    in parallel.mesh2d (DESIGN.md §15).

`devices=None` resolves lazily against jax.devices() (under jax.distributed
that is the global device list, so multi-host placement is the same
spelling). On the host CPU backend, a 2-D topology that does not fit the
visible devices runs as a sequential loop over replicas — bit-identical
to the sharded execution, which is how single-device CI covers every
topology. On an accelerator too few devices is an error: a loop there
would hide a missing chip.

FleetSpec normalizes the legacy spellings onto this type (with a
DeprecationWarning) so old and new specs compare EQUAL — the migration
table lives in DESIGN.md §9.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np
import jax
from jax.sharding import Mesh

# Axis names. The lane axis keeps the 1-D mesh's historical name so cached
# shardings/meshes from group_sharding stay interchangeable.
DATA_AXIS = "data"
LANE_AXIS = "groups"

PLACEMENTS = ("single", "sharded", "mesh2d")


@dataclasses.dataclass(frozen=True)
class TopologySpec:
    """Declarative (data × lane) placement for a fleet.

    data    — stream replicas along the data axis (disjoint chunk shards,
              merged by the pinned rule). 1 = no data parallelism.
    lanes   — lane-axis shards. 1 = lanes unsharded.
    devices — None (resolve against jax.devices() at spec-build time), an
              int (take the first N devices), or an explicit device tuple
              (multi-host: pass the jax.distributed global devices).

    Hashable and frozen: rides as static metadata on FleetSpec and on the
    Mesh2DFleet pytree.
    """

    data: int = 1
    lanes: int = 1
    devices: Optional[Tuple] = None

    def __post_init__(self):
        data = int(self.data)
        lanes = int(self.lanes)
        if data < 1 or lanes < 1:
            raise ValueError(
                f"TopologySpec axes must be >= 1, got data={self.data} "
                f"lanes={self.lanes}")
        object.__setattr__(self, "data", data)
        object.__setattr__(self, "lanes", lanes)
        devs = self.devices
        if devs is not None and not isinstance(devs, (int, np.integer)):
            devs = tuple(devs)
            if len(devs) != data * lanes:
                raise ValueError(
                    f"TopologySpec(data={data}, lanes={lanes}) needs "
                    f"{data * lanes} devices, got {len(devs)} explicitly")
            object.__setattr__(self, "devices", devs)

    # ------------------------------------------------------------- placement
    @property
    def placement(self) -> str:
        """'single' | 'sharded' (1-D lane mesh) | 'mesh2d' (data × lane)."""
        if self.data > 1:
            return "mesh2d"
        return "sharded" if self.lanes > 1 else "single"

    @property
    def num_devices(self) -> int:
        return self.data * self.lanes

    def describe(self) -> dict:
        """JSON-able stanza (checkpoint manifests, service stats)."""
        return {"data": self.data, "lanes": self.lanes,
                "placement": self.placement}

    # ------------------------------------------------------------ resolution
    def resolve(self) -> "TopologySpec":
        """Pin `devices` to a concrete tuple (or None).

        single          — devices forced to None (nothing to place).
        sharded (1-D)   — exactly `lanes` devices, resolved from
                          jax.devices() when unspecified; too few is an
                          error (the 1-D mesh's historical contract).
        mesh2d          — `data · lanes` devices when available; when
                          jax.devices() cannot cover the shape and no
                          explicit devices were given, devices stays None
                          and a CPU backend runs the sequential replica
                          loop (bit-identical — parallel.mesh2d). On any
                          other backend too few devices is an error.
        """
        if self.placement == "single":
            return self if self.devices is None else \
                dataclasses.replace(self, devices=None)
        need = self.num_devices
        devs = self.devices
        if isinstance(devs, (int, np.integer)):
            if int(devs) != need:
                raise ValueError(
                    f"TopologySpec(data={self.data}, lanes={self.lanes}) "
                    f"needs {need} devices, got devices={devs}")
            devs = None
        if devs is not None:
            return self if devs == self.devices else \
                dataclasses.replace(self, devices=devs)
        avail = jax.devices()
        if len(avail) < need:
            if self.placement == "sharded" or avail[0].platform != "cpu":
                raise ValueError(
                    f"TopologySpec(data={self.data}, lanes={self.lanes}) "
                    f"needs {need} devices, found {len(avail)} "
                    f"{avail[0].platform} device(s)")
            return dataclasses.replace(self, devices=None)  # CPU loop
        return dataclasses.replace(self, devices=tuple(avail[:need]))

    @property
    def on_devices(self) -> bool:
        """True when a resolved non-single topology holds a device tuple
        (shard_map execution); False = sequential loop fallback."""
        return isinstance(self.devices, tuple)

    # ----------------------------------------------------------------- meshes
    def mesh1d(self) -> Mesh:
        """1-D lane mesh (placement 'sharded') — group_sharding's mesh."""
        if self.placement != "sharded":
            raise ValueError(f"mesh1d() on a {self.placement} topology")
        t = self.resolve()
        return Mesh(np.asarray(t.devices), (LANE_AXIS,))

    def mesh2d(self) -> Mesh:
        """2-D (data × lane) mesh (placement 'mesh2d', device-resolved)."""
        if self.placement != "mesh2d":
            raise ValueError(f"mesh2d() on a {self.placement} topology")
        t = self.resolve()
        if not t.on_devices:
            raise ValueError(
                f"TopologySpec(data={self.data}, lanes={self.lanes}) is in "
                f"loop-fallback mode ({len(jax.devices())} device(s) "
                f"visible) — no device mesh to build")
        return Mesh(np.asarray(t.devices).reshape(self.data, self.lanes),
                    (DATA_AXIS, LANE_AXIS))

    # --------------------------------------------------------------- mappers
    @staticmethod
    def single() -> "TopologySpec":
        return TopologySpec()

    @staticmethod
    def from_mesh(mesh: Optional[Mesh]) -> "TopologySpec":
        """Map a legacy 1-D `mesh=` (or None = all devices) onto a spec —
        the FleetSpec deprecation shim's half of 'EQUAL specs'."""
        if mesh is None:
            return TopologySpec(lanes=len(jax.devices()))
        devs = tuple(np.asarray(mesh.devices).reshape(-1))
        return TopologySpec(lanes=len(devs), devices=devs)


__all__ = ["DATA_AXIS", "LANE_AXIS", "PLACEMENTS", "TopologySpec"]
