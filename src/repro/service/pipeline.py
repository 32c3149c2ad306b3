"""Async host→device ingest: double-buffered chunk staging over the fleet.

The pipeline has three stages, overlapped two-deep:

  stage 0  SOURCE   — the caller's chunk iterator draws/receives the next
                      [t, G] host block (network read, RNG draw, ...);
  stage 1  STAGE    — a put-ahead thread (`data.pipeline.prefetch_to_device`
                      — the same primitive the train loop uses) moves the
                      block to device while the previous chunk computes;
  stage 2  APPLY    — the ingest thread runs `fleet.ingest(chunk)` and
                      blocks on the result, which is the pipeline's
                      backpressure: at most `depth` staged chunks + one in
                      compute are ever alive, so host memory stays bounded
                      no matter how fast the source is.

Each applied chunk yields a NEW immutable fleet (functional ingest); the
`on_chunk` callback is where the server publishes that version for
readers. Blocking per chunk is deliberate: it gives honest per-chunk
latency numbers and a real publication point — an unbounded dispatch queue
would "publish" fleets whose device work hasn't happened yet.

Where a chunk is staged is the fleet's to say (`QuantileFleet.stage`):
the single placement takes it whole with `jax.device_put`, as before; a
lane-sharded fleet puts each device's columns on that device, so no
device ever holds a whole chunk.

Spans (`telemetry.span`, recorded while a profiler capture runs), each
keyed by the chunk's number: `ingest.stage` (the put-ahead thread's
`fleet.stage` of one chunk; `jax.device_put` returns once the copy is
under way, a lane-sharded stage once every device holds its columns)
and, on a lane-sharded fleet, its children `ingest.stage.shard` (the
wait for one device's columns), `ingest.wait_staged` (the apply loop
waiting for the next staged chunk), `ingest.apply` (`fleet.ingest` plus
the wait on its result) and its child `ingest.block` (that wait alone:
the device working on the chunk).

Telemetry (optional, duck-typed): items/chunks counters, the
`ingest.bytes_staged` counter, and each `ingest.apply` span's duration
into the `ingest_chunk_ms` histogram.
"""
from __future__ import annotations

import itertools
from typing import Callable, Iterable, Optional

import jax
import numpy as np

from repro.api.fleet import QuantileFleet
from repro.data.pipeline import prefetch_to_device

from .telemetry import span


def _block_on(fleet: QuantileFleet) -> None:
    """Wait for the fleet's device work (publication barrier). Every state
    plane is an output of one executable, so waiting on `m` waits for all
    of it; on a sharded fleet `m` is the global array, whose readiness is
    that of every shard on every device."""
    state = fleet.state
    sk = getattr(state, "sketch", state)   # sharded fleets wrap the sketch
    jax.block_until_ready(sk.m)


class IngestPipeline:
    """Double-buffered host→device chunk ingest over one QuantileFleet.

    `depth` is the put-ahead queue bound (1 = classic double buffering).
    """

    def __init__(self, depth: int = 1, telemetry=None):
        self.depth = int(depth)
        self.telemetry = telemetry
        self._applied = 0

    def run(self, fleet: QuantileFleet, chunks: Iterable,
            on_chunk: Optional[Callable] = None) -> QuantileFleet:
        """Drive `chunks` ([t, G] blocks) through `fleet`; returns the final
        fleet. `on_chunk(new_fleet, n_items)` fires after each chunk's
        device work completes — the server's publication hook."""
        tel = self.telemetry
        # Chunk numbers count every chunk this pipeline has applied; staging
        # runs ahead in its own thread but in the same order.
        first = self._applied
        numbers = itertools.count(first)
        place = fleet.stage

        def shard_span():
            return span("ingest.stage.shard")

        def transfer(x):
            with span("ingest.stage", key=next(numbers)):
                out = place(x, shard_span)
            if tel is not None:
                tel.count("ingest.bytes_staged", out.nbytes)
            return out

        staged = prefetch_to_device(iter(chunks), depth=self.depth,
                                    transfer=transfer)
        for k in itertools.count(first):
            with span("ingest.wait_staged", key=k) as waited:
                chunk = next(staged, None)
                if chunk is None:
                    waited.drop()
            if chunk is None:
                break
            n = int(np.shape(chunk)[0])
            with span("ingest.apply", key=k) as applied:
                fleet = fleet.ingest(chunk)
                with span("ingest.block"):
                    _block_on(fleet)
            self._applied = k + 1
            if tel is not None:
                tel.observe_ms("ingest_chunk_ms", applied.ms)
                tel.count("items_ingested", n)
                tel.count("chunks_ingested")
            if on_chunk is not None:
                on_chunk(fleet, n)
        return fleet
