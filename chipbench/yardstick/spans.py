"""The program's own span log, read by the `program_span` metrics.

The service records its spans (`repro.service.telemetry.span`) into an
in-memory log while a profiler capture runs; `recorded_spans()` returns
the newest capture's. A traced run's capture is the window, so after it
the log holds the window's spans: each a record with `name`, `key` (the
chunk's or the read's number), `thread`, `start_ns` and `end_ns` on the
program's `perf_counter_ns` clock. That clock is not the device trace's,
so the readers measure durations and gaps within the log only.

Every reader returns None where the run was not traced, where the program
keeps no span log, where the log dropped records, or where the spans it
needs are missing.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def records(run) -> Optional[Sequence]:
    """The traced window's span records, or None."""
    if run.trace is None:
        return None
    try:
        from repro.service.telemetry import recorded_spans
    except ImportError:
        return None
    log = recorded_spans()
    return None if log.dropped else log.spans


def named(spans: Sequence, name: str) -> list:
    return [r for r in spans if r.name == name]


def durations_ms(spans: Sequence, name: str) -> List[float]:
    return [(r.end_ns - r.start_ns) / 1e6 for r in named(spans, name)]


def median_ms(run, name: str) -> Optional[float]:
    """Median duration of the spans called `name`."""
    spans = records(run)
    ms = durations_ms(spans, name) if spans is not None else []
    return float(np.median(ms)) if ms else None


def gaps_ms(spans: Sequence, name: str) -> List[float]:
    """Time on one thread from the end of each span called `name` to the
    start of the next one, whose key is one more."""
    by_thread: dict = {}
    for r in named(spans, name):
        by_thread.setdefault(r.thread, []).append(r)
    out = []
    for rs in by_thread.values():
        rs.sort(key=lambda r: r.start_ns)
        out += [(b.start_ns - a.end_ns) / 1e6 for a, b in zip(rs, rs[1:])
                if a.key is not None and b.key == a.key + 1]
    return out
