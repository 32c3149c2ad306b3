"""Service observability: monotonic counters, latency histograms tracked
by a frugal fleet on its OWN metrics, and spans that a profiler capture
records.

The counters are plain thread-safe dict increments (ingest and query
threads both write them): `items_ingested`, `chunks_ingested`,
`ingest.bytes_staged`, `queries_served`, `queries_stalled`,
`quarantined_lanes`; callers may add their own. The latency distribution
is where we eat our own dogfood: per-metric p50/p99 come from a tiny
scalar-clock `repro.api.QuantileFleet` — one group per latency metric,
quantile lanes (0.5, 0.99) — fed NaN-padded [rounds, metrics] blocks (NaN
is the stack's bit-exact no-op padding contract), so the service's
*telemetry* costs 2 words per (metric × quantile) lane, exactly the
paper's claim applied to ourselves.

Determinism note: a latency lane's trajectory is a pure function of the
sequence of (flush boundary, observed values) — the counter RNG keys each
round on the fleet cursor's absolute tick, so replaying the same
observations through the same flush pattern replays the same histogram.
Wall-clock latencies themselves are of course not deterministic; the
MACHINERY is.

Spans. `span(name, key=None)` times one region of the served path (the
ingest pipeline's stage and each device's part of a sharded stage, wait,
apply and block; a read, its snapshot and its DP release); the histograms
above take their durations from it. To
see the spans themselves, start a `jax.profiler` capture
(`jax.profiler.start_trace(dir)` ... `stop_trace()`). While one runs,
each span is also a `TraceAnnotation` in the profile's host plane, on the
device trace's clock, and one record in an in-memory log that
`recorded_spans()` returns: the newest capture's spans only, bounded at
`SPAN_LOG_LIMIT` records with the overflow counted. With no capture
running nothing is recorded, and a span costs the profiler's enabled
check on top of the timing the histograms need. `stats()` does not
change with a capture.

`runtime_metadata()` is the shared run-record stamp (wall-clock, device
count, backend, versions) every `BENCH_*.json` embeds via
`benchmarks.common.write_bench_json` — one definition instead of each
bench re-rolling its own ad hoc metadata.
"""
from __future__ import annotations

import itertools
import os
import platform as _platform
import threading
import time
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np
from jax._src import profiler as _jax_profiler
from jax.profiler import TraceAnnotation

from repro.api.fleet import QuantileFleet
from repro.api.spec import FleetSpec

DEFAULT_LATENCY_METRICS: Tuple[str, ...] = ("ingest_chunk_ms", "query_ms")
LATENCY_QUANTILES: Tuple[float, ...] = (0.5, 0.99)


class Telemetry:
    """Thread-safe counters + frugal latency histograms.

    One instance is shared by a service's ingest thread, its query callers,
    and (duck-typed, via `telemetry=`) serve.SLOFleet — anything with
    `count(name, n)` fits that slot, so serve never imports this package.
    """

    def __init__(self, metrics: Sequence[str] = DEFAULT_LATENCY_METRICS,
                 seed: int = 0):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._metrics = tuple(str(m) for m in metrics)
        if len(set(self._metrics)) != len(self._metrics):
            raise ValueError(f"duplicate latency metrics in {metrics}")
        self._metric_idx = {m: i for i, m in enumerate(self._metrics)}
        self._pending: Dict[str, list] = {m: [] for m in self._metrics}
        # One group per metric, a (p50, p99) quantile lane pair each.
        self._fleet = QuantileFleet.create(
            FleetSpec(num_groups=max(1, len(self._metrics)),
                      quantiles=LATENCY_QUANTILES, backend="jnp"),
            seed=int(seed))

    # -------------------------------------------------------------- counters
    def count(self, name: str, n: int = 1) -> None:
        """Monotonically bump counter `name` by `n` (n >= 0)."""
        n = int(n)
        if n < 0:
            raise ValueError(f"counters are monotonic; count({name!r}, {n})")
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    # ------------------------------------------------------------- latencies
    def observe_ms(self, metric: str, ms: float) -> None:
        """Buffer one latency observation (host-side, no device work)."""
        if metric not in self._metric_idx:
            raise KeyError(f"unknown latency metric {metric!r}; have "
                           f"{self._metrics}")
        with self._lock:
            self._pending[metric].append(float(ms))

    def _flush_locked(self) -> None:
        rounds = max((len(v) for v in self._pending.values()), default=0)
        if rounds == 0:
            return
        g = self._fleet.num_groups
        block = np.full((rounds, g), np.nan, np.float32)
        for m, gi in self._metric_idx.items():
            vals = self._pending[m]
            if vals:
                block[:len(vals), gi] = np.asarray(vals, np.float32)
            self._pending[m] = []
        self._fleet = self._fleet.ingest(block)

    def flush(self) -> None:
        """Apply buffered observations as one NaN-padded block ingest."""
        with self._lock:
            self._flush_locked()

    def latency_quantiles(self) -> Dict[str, Dict[str, float]]:
        """{metric: {"p50": ..., "p99": ...}} from the frugal lanes."""
        with self._lock:
            self._flush_locked()
            plane = self._fleet.estimate()       # [metrics, 2]
        return {m: {"p50": float(plane[gi, 0]), "p99": float(plane[gi, 1])}
                for m, gi in self._metric_idx.items()}

    # --------------------------------------------------------------- readout
    def snapshot(self) -> Dict[str, object]:
        """One coherent observability readout (counters + latency
        quantiles) — what server.py exposes and benches record."""
        return {
            "counters": self.counters(),
            "latency_ms": self.latency_quantiles(),
        }


# ------------------------------------------------------------------- spans
SPAN_LOG_LIMIT = 1 << 16          # records kept per capture


class SpanRecord(NamedTuple):
    """One finished span of a capture; times from `time.perf_counter_ns()`.
    `parent_id` is the enclosing span's id on the same thread (None at the
    top); `key` is the chunk's number for ingest spans, the read's number
    for query spans."""

    name: str
    span_id: int
    parent_id: Optional[int]
    key: Optional[int]
    thread: str
    start_ns: int
    end_ns: int


class SpanLog(NamedTuple):
    """The newest capture's spans in the order they ended; `dropped`
    counts the records past `SPAN_LOG_LIMIT`, which were left out."""

    spans: Tuple[SpanRecord, ...]
    dropped: int


def _session():
    """The capture `jax.profiler.start_trace` runs in this process, or None
    (none runs, or a remote client started it through the profiler
    server)."""
    state = getattr(_jax_profiler, "_profile_state", None)
    return getattr(state, "profile_session", None)


class _Log:
    def __init__(self):
        self.lock = threading.Lock()
        self.capture = None        # token of the capture being recorded
        self.records: list = []
        self.dropped = 0
        self.ids = itertools.count(1)

    def join(self):
        """The running capture's token; a capture not seen before starts
        a fresh log."""
        session = _session()
        with self.lock:
            if self.capture is None or (session is not None
                                        and session is not self.capture):
                self.capture = object() if session is None else session
                self.records, self.dropped = [], 0
            return self.capture

    def leave(self) -> None:
        """Seen with no capture running: the next capture starts a fresh
        log (for one that `_session` cannot tell apart)."""
        with self.lock:
            if not _capturing():
                self.capture = None

    def add(self, capture, record: SpanRecord) -> None:
        with self.lock:
            if capture is not self.capture:
                return
            if len(self.records) < SPAN_LOG_LIMIT:
                self.records.append(record)
            else:
                self.dropped += 1


_LOG = _Log()
_LOCAL = threading.local()          # .stack: the thread's open spans
_capturing = TraceAnnotation.is_enabled


class span:
    """`with span(name, key=None) as s:` times a region; `s.ms` is its
    duration after the block. While a profiler capture runs, the region is
    also a `TraceAnnotation` and a `SpanRecord` in `recorded_spans()`; a
    `key` left None takes the enclosing span's."""

    __slots__ = ("name", "key", "start_ns", "end_ns", "_capture", "_id",
                 "_parent", "_note", "_keep")

    def __init__(self, name: str, key: Optional[int] = None):
        self.name, self.key = name, key
        self._capture = None
        self._keep = True

    def __enter__(self) -> "span":
        if _capturing():
            self._open()
        elif _LOG.capture is not None:
            _LOG.leave()
        self.start_ns = time.perf_counter_ns()
        return self

    def _open(self) -> None:
        self._capture = _LOG.join()
        stack = _LOCAL.__dict__.setdefault("stack", [])
        parent = stack[-1] if stack else None
        self._parent = None if parent is None else parent._id
        if self.key is None and parent is not None:
            self.key = parent.key
        self._id = next(_LOG.ids)
        stack.append(self)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._capture is None:
            return
        self._note.__exit__(*exc)
        _LOCAL.stack.pop()
        if self._keep:
            _LOG.add(self._capture, SpanRecord(
                self.name, self._id, self._parent, self.key,
                threading.current_thread().name, self.start_ns,
                self.end_ns))

    def drop(self) -> None:
        """Leave this span out of `recorded_spans()` (the profile keeps
        it): for a region that turned out not to be the one named."""
        self._keep = False

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


def recorded_spans() -> SpanLog:
    """The spans of the newest profiler capture (of the running one, while
    it runs); empty before any capture."""
    with _LOG.lock:
        return SpanLog(tuple(_LOG.records), _LOG.dropped)


def runtime_metadata() -> Dict[str, object]:
    """Self-describing run-record stamp: wall-clock, device count, backend,
    versions. Embedded in every BENCH_*.json (benchmarks.common) so the
    perf trajectory files say WHERE each number came from."""
    import jax

    return {
        "unix_time": float(time.time()),
        "wall_clock_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "device_count": int(jax.device_count()),
        "backend": str(jax.default_backend()),
        "jax_version": str(jax.__version__),
        "python_version": _platform.python_version(),
        "cpu_count": int(os.cpu_count() or 1),
    }
