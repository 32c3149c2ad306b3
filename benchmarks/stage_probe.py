"""How fast a lane-sharded fleet's chunk reaches its devices, by staging
method, on the devices of this host (meant for a four-chip TPU host).

A row-major [T, G] float32 host chunk is split by column over the n
devices; each method puts every device's [T, G/n] slab on that device:

    sharding  `jax.device_put(chunk, NamedSharding(mesh, P(None, axis)))`:
              each slab is a strided view of the chunk
    rows      the program's staging, `QuantileFleet.stage` on a
              lane-sharded fleet: each slab sent as T contiguous row
              pieces and stacked on the device
    buffers   each slab copied (one thread per device) into a contiguous
              host buffer made and touched once before the first chunk
              and reused for every chunk, then sent whole

A pool of two chunks drawn in the main thread, as the benchmark's traffic
draws them, is staged alternately, `--chunks` times per method, in the
order given by `--methods` (a method named twice is measured twice, to see
drift within the process). Per chunk: the seconds from the first call to
every slab on its device, and each device's own seconds to that point.
Prints one JSON line.

    python benchmarks/stage_probe.py [--groups 16777216] [--t 64]
        [--chunks 6] [--methods sharding,rows,buffers,sharding,rows]

(with `PYTHONPATH=src`, from the repository's root).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro.api import FleetSpec, QuantileFleet
from repro.parallel.topology import TopologySpec


def _landed(arrays, t0):
    """Seconds from t0 to each array's readiness, one waiting thread each."""
    out = [None] * len(arrays)

    def wait(i):
        arrays[i].block_until_ready()
        out[i] = time.perf_counter() - t0

    threads = [threading.Thread(target=wait, args=(i,))
               for i in range(len(arrays))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=1 << 24)
    ap.add_argument("--t", type=int, default=64)
    ap.add_argument("--chunks", type=int, default=6)
    ap.add_argument("--methods", default="sharding,rows,buffers,sharding,rows")
    args = ap.parse_args(argv)

    devices = jax.devices()
    n = len(devices)
    spec = FleetSpec(num_groups=args.groups, quantiles=(0.5, 0.99),
                     chunk_t=args.t, topology=TopologySpec(lanes=n))
    fleet = QuantileFleet.create(spec)
    sharding = fleet.item_sharding
    width = args.groups // n

    rng = np.random.default_rng(1)
    t_draw = time.perf_counter()
    pool = []
    for _ in range(2):
        x = rng.standard_normal((args.t, args.groups), dtype=np.float32)
        np.exp(x, out=x)
        pool.append(x)
    draw_s = time.perf_counter() - t_draw

    buffers = None
    copier = ThreadPoolExecutor(n)

    def by_sharding(x, t0):
        out = jax.device_put(x, sharding)
        return _landed([s.data for s in out.addressable_shards], t0)

    def by_rows(x, t0):
        ends = []

        class mark:
            def __enter__(self):
                return self

            def __exit__(self, *exc):
                ends.append(time.perf_counter() - t0)

        fleet.stage(x, mark)
        return ends

    def by_buffers(x, t0):
        def put(d):
            buf = buffers[d]
            np.copyto(buf, x[:, d * width:(d + 1) * width])
            slab = jax.device_put(buf, devices[d])
            slab.block_until_ready()
            return time.perf_counter() - t0

        return list(copier.map(put, range(n)))

    methods = {"sharding": by_sharding, "rows": by_rows,
               "buffers": by_buffers}
    runs = []
    for name in args.methods.split(","):
        if name == "buffers" and buffers is None:
            buffers = [np.ones((args.t, width), np.float32) for _ in range(n)]
        # One chunk unmeasured first: compiles and first transfers.
        methods[name](pool[1], time.perf_counter())
        chunk_s, device_s = [], []
        for k in range(args.chunks):
            t0 = time.perf_counter()
            per_device = methods[name](pool[k % 2], t0)
            chunk_s.append(time.perf_counter() - t0)
            device_s.append(per_device)
        runs.append({"method": name, "chunk_s": chunk_s,
                     "median_s": statistics.median(chunk_s),
                     "device_s": device_s})
    copier.shutdown()
    print(json.dumps({"stage_probe": runs, "devices": n,
                      "kind": devices[0].device_kind,
                      "chunk_bytes": pool[0].nbytes, "draw_s": draw_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
