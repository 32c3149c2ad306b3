"""Profiler capture and its reduction to per-layer numbers.

A traced run records one `jax.profiler` capture over the measured window
(Python tracer off, so per-event Python calls do not flood it), reads the
`.xplane.pb` back with `jax.profiler.ProfileData`, keeps the events the
readers need, and deletes the files. Busy time is the union of device-op
intervals; a kernel's time is the sum of its events' durations. A kernel
is a device op that XLA ran as a custom call: its name or one of its
stats holds the op's HLO text with `custom-call(` or `custom_call_target`
in it. On a TPU v5e the op event's name is that HLO text, and XLA names
the custom call after the function that wraps the `pallas_call`: the
dense kernel's op is `%_blocked_jit.<n> = ... custom-call(...)`
(kernels/ops.py) inside the `jit__ingest_array_scan(<id>)` executable
(fixtures/trace-backfill-v5e.json).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Host spans the benchmark itself records around its calls.
ANNOTATIONS = ("generate", "read")
# A device op that is a custom call (a Pallas kernel): by its name, or by
# its own HLO text, which is the event's name on a TPU or else a stat (an
# op that only reads a custom call's result names it as an operand, never
# with "custom-call(").
KERNEL_NAME = re.compile(r"(?i)^(custom-call|_blocked_jit)\b|pallas")
CALL_TEXT = re.compile(r"custom-call\(|custom_call_target")
# The executable the dense ingest runs in; its only custom call is the
# dense Pallas kernel.
INGEST_MODULE = r"ingest_array_scan"


@dataclasses.dataclass
class TraceData:
    """What the readers see of one traced window."""

    window_s: float
    ops: Dict[int, List[Event]]            # device id -> XLA op events
    modules: Dict[int, List[Event]]        # device id -> executable events
    spans: List[Event]                     # the benchmark's host spans
    kernels: Dict[int, List[Event]] = dataclasses.field(
        default_factory=dict)              # device id -> custom-call ops

    @property
    def device_ids(self) -> List[int]:
        return sorted(self.ops)


def union_ns(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(td: TraceData) -> float:
    """Seconds in which some op ran, averaged over the traced devices."""
    if not td.ops:
        return 0.0
    per = [union_ns([(s, e) for _, s, e in evs]) for evs in td.ops.values()]
    return sum(per) / len(per) / 1e9


def matching(events: Sequence[Event], pattern: str) -> List[Event]:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def inside(events: Sequence[Event],
           containers: Sequence[Event]) -> List[Event]:
    """Events whose interval lies within one of `containers`."""
    spans = sorted((s, e) for _, s, e in containers)
    out, j = [], 0
    for ev in sorted(events, key=lambda x: x[1]):
        while j < len(spans) and spans[j][1] < ev[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= ev[1] and ev[2] <= spans[j][1]:
            out.append(ev)
    return out


def total_ms(events: Sequence[Event]) -> float:
    return sum(e - s for _, s, e in events) / 1e6


def per_device_mean(td: TraceData, fn) -> Optional[float]:
    """Mean over devices of fn(device_id); None where any device reads
    nothing."""
    vals = [fn(d) for d in td.device_ids]
    if not vals or any(v is None for v in vals):
        return None
    return sum(vals) / len(vals)


def kernel_mean_ms(td: TraceData, module: str) -> Optional[float]:
    """Mean device time of one call of the custom-call kernels inside the
    executable named by `module`, averaged over the devices."""

    def per_device(d):
        evs = inside(td.kernels.get(d, []), matching(td.modules[d], module))
        return total_ms(evs) / len(evs) if evs else None

    return per_device_mean(td, per_device)


def is_custom_call(ev) -> bool:
    """A profiler event of a device op that XLA ran as a custom call. On
    a TPU the op's event name is its HLO text."""
    if KERNEL_NAME.search(ev.name) or CALL_TEXT.search(ev.name):
        return True
    for key, value in ev.stats:
        if isinstance(value, str) and (
                CALL_TEXT.search(value)
                or (key == "hlo_category" and "custom" in value)):
            return True
    return False


def top_ops(td: TraceData, n: int = 10) -> List[list]:
    """Device ops that took most time, seconds averaged over devices."""
    acc: Dict[str, float] = {}
    for evs in td.ops.values():
        for name, s, e in evs:
            acc[name] = acc.get(name, 0.0) + (e - s)
    k = max(len(td.ops), 1)
    return [[name, ns / k / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(td: TraceData, n: int = 10) -> List[list]:
    """The longest device-idle gaps (first device), each named by the host
    span that overlaps it most ("none" where no span does)."""
    if not td.ops:
        return []
    dev = td.device_ids[0]
    iv = sorted((s, e) for _, s, e in td.ops[dev])
    gaps, end = [], None
    for s, e in iv:
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:n]:
        best, best_ov = "none", 0.0
        for name, s, e in td.spans:
            ov = min(ge, e) - max(gs, s)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, (ge - gs) / 1e9])
    return out


def reduce_xspace(path: str, window_s: float) -> TraceData:
    """Read one `.xplane.pb` into TraceData."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window_s)


def reduce_planes(planes, window_s: float) -> TraceData:
    """TraceData of profiler planes: each has `name` and `lines`, each line
    `name` and `events`, each event `name`, `start_ns`, `end_ns` and
    `stats` ((key, value) pairs)."""
    ops, modules, kernels, spans = {}, {}, {}, []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            ops[dev], modules[dev], kernels[dev] = [], [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = []
                for ev in line.events:
                    rec = (ev.name, float(ev.start_ns), float(ev.end_ns))
                    evs.append(rec)
                    if line.name == OPS_LINE and is_custom_call(ev):
                        kernels[dev].append(rec)
                if line.name == OPS_LINE:
                    ops[dev] = evs
                else:
                    modules[dev] = evs
            if not ops[dev]:        # no op line: executables are the ops
                ops[dev] = modules[dev]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    rec = (ev.name, float(ev.start_ns), float(ev.end_ns))
                    if ev.name in ANNOTATIONS:
                        spans.append(rec)
    return TraceData(window_s=window_s, ops=ops, modules=modules,
                     spans=spans, kernels=kernels)


class Capture:
    """One profiler capture in a scratch directory under TMPDIR."""

    def __init__(self):
        self._dir = None
        self._t0 = None

    def start(self) -> None:
        import time

        import jax

        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> TraceData:
        import time

        import jax

        # Read before stopping: writing the trace out is not traced time.
        window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        try:
            return reduce_xspace(find_xspace(self._dir), window_s)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def span(name: str):
    """A host span in the profiler's trace, around one of the benchmark's
    own calls (`ANNOTATIONS`)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def find_xspace(root: str) -> str:
    files = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]
