"""The dense GROUPBY fleet of `dense_service`, lane-sharded over the chips.

The configuration's `topology` (`{"lanes": n}`) places the fleet with
`TopologySpec(lanes=n)`: the flattened lane axis cut n ways, each chip
holding its own lanes and fed its own column slab of every chunk by the
service's staging. Traffic, window, check and reference are
`dense_service`'s; three things differ:

    set-up        the fleet's `FleetSpec` carries the topology
    lane sample   SHARD_BLOCKS blocks of SAMPLE_WIDTH lanes inside each
                  shard's lane range, so the check covers every chip
    info          each chip's peak device memory after the window

A program that stages every chunk whole on one device (no
`QuantileFleet.stage`) cannot hold this fleet's chunks: the run stops
before any device work.
"""
from __future__ import annotations

import time

import numpy as np

from yardstick import harness
from yardstick.checks import sample_blocks
from yardstick.traffic import flow_size_chunks, rng_for

dense_service = harness.load_module("systems", "dense_service")

SAMPLE_WIDTH = dense_service.SAMPLE_WIDTH
SHARD_BLOCKS = 4


def sample_lanes(seed: int, num_lanes: int, shards: int) -> np.ndarray:
    """SHARD_BLOCKS seeded runs of SAMPLE_WIDTH lanes inside each of the
    `shards` equal lane ranges, in lane order."""
    if num_lanes % shards:
        raise ValueError(f"{num_lanes} lanes do not cut {shards} ways")
    width = num_lanes // shards
    rng = rng_for(seed, 1)
    starts = [k * width + s for k in range(shards)
              for s in sample_blocks(rng, width, SHARD_BLOCKS, SAMPLE_WIDTH)]
    return np.concatenate([np.arange(s, s + SAMPLE_WIDTH) for s in starts])


class System(dense_service.System):
    def __init__(self, config, traffic, *, seed, seconds, run):
        from repro.api import QuantileFleet

        if not hasattr(QuantileFleet, "stage"):
            raise RuntimeError(
                "the program stages each chunk whole on one device (no "
                "QuantileFleet.stage); a lane-sharded fleet of "
                f"{config['num_groups']} groups needs each chip's columns "
                "staged on that chip")
        super().__init__(config, traffic, seed=seed, seconds=seconds,
                         run=run)
        self.shards = int(config["topology"]["lanes"])

    # ------------------------------------------------------------------ setup
    def setup(self):
        from repro.api import FleetSpec
        from repro.parallel.topology import TopologySpec
        from repro.service import StreamingService, TenantPolicy

        class TimedService(StreamingService):
            """Records when each fleet version becomes visible."""

            def __init__(self, *a, **kw):
                self.published = []
                super().__init__(*a, **kw)

            def _publish(self, fleet, n_items):
                super()._publish(fleet, n_items)
                self.published.append(time.perf_counter())

        dist = self.config["items"]
        rng = rng_for(self.seed, 0)
        self.pool = list(flow_size_chunks(
            self.groups, int(self.traffic["pool_chunks"]), self.chunk_t, rng,
            mu_range=dist["mu"], sigma_range=dist["sigma"]))
        spec = FleetSpec(num_groups=self.groups, quantiles=self.quantiles,
                         chunk_t=self.chunk_t, program=self.config["program"],
                         topology=TopologySpec(lanes=self.shards))
        tenants = [TenantPolicy(n, epsilon=e) for n, e in self.epsilon.items()]
        self.svc = TimedService(spec, seed=self.pseed, tenants=tenants)
        self.devices = list(spec.topology.devices)
        # Warm-up: the first pool chunk through the service's own ingest,
        # then one read of each kind the window will make.
        self.sequence = [0]
        self.svc.ingest(self.pool[0])
        for tenant, q in dict.fromkeys(self.read_mix):
            self.svc.query(tenant, quantile=float(q))
        self.sample_lanes = sample_lanes(
            self.seed, self.groups * len(self.quantiles), self.shards)
        self.sample_groups = np.unique(self.sample_lanes // len(self.quantiles))

    # ------------------------------------------------------------------- info
    def info(self) -> dict:
        out = super().info()
        out["memory_peak_bytes_per_device"] = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devices]
        return out
