"""repro.service — the composed streaming service (PR 8).

Async double-buffered host→device ingest (`IngestPipeline`) feeding one
`QuantileFleet`, concurrent consistent reads (`Snapshot` copy-on-query of
the query planes), per-tenant DP gating (`TenantPolicy` through the
`2u-dp` program), and live observability (`Telemetry`: monotonic counters
+ frugal latency histograms; `span`s that a `jax.profiler` capture records
into `recorded_spans()`). `StreamingService` wires them together.
DESIGN.md §14 documents the snapshot protocol and fault guarantees;
benchmarks/bench_service_e2e.py (e14) gates concurrent-query throughput
and the bit-exact-replay audit of every served answer.
"""
from .pipeline import IngestPipeline
from .server import INTERNAL, StreamingService, TenantPolicy
from .snapshot import Snapshot
from .telemetry import Telemetry, recorded_spans, runtime_metadata, span

__all__ = [
    "IngestPipeline", "Snapshot", "StreamingService", "TenantPolicy",
    "INTERNAL", "Telemetry", "recorded_spans", "runtime_metadata", "span",
]
