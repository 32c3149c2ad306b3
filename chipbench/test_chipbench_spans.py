"""The `program_span` metrics: each reads the program's own span log
(yardstick/spans.py) in a traced run, and reads nothing without one."""
import json
import os
from types import SimpleNamespace

import pytest

from yardstick import harness
from yardstick import spans
from yardstick import trace as tr

CELLS = ("groupby-backfill", "groupby-reads")
SPAN_METRICS = tuple(m["name"] for m in harness.load_benchmark()["per_layer"]
                     if m["source"] == "program_span")


def _rec(name, key, start_ms, end_ms, thread="service-ingest"):
    return SimpleNamespace(name=name, key=key, thread=thread,
                           start_ns=int(start_ms * 1e6),
                           end_ns=int(end_ms * 1e6))


@pytest.mark.parametrize("workload", CELLS)
def test_traced_tiny_run_reports_every_span_metric(run_tiny, workload):
    res = run_tiny(workload, traced=True)
    assert res["correct"], res["checks"]
    bench = harness.load_benchmark()
    want = [m["name"] for m in harness.metrics_of(bench, workload, True)
            if m["source"] == "program_span"]
    assert want and all(name in res["metrics"] for name in want), \
        res["metrics"]
    for name in want:
        assert res["metrics"][name]["value"] >= 0


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_reader_reads_nothing_without_a_window_log(monkeypatch, metric):
    from repro.service import telemetry

    read = harness.load_module("metrics", metric).read
    assert read(SimpleNamespace(trace=None)) is None
    traced = SimpleNamespace(trace=object())
    monkeypatch.setattr(telemetry, "recorded_spans",
                        lambda: telemetry.SpanLog((), 0))
    assert read(traced) is None
    full = tuple(SimpleNamespace(name=n, key=0, thread="t", start_ns=0,
                                 end_ns=1) for n in (
        "ingest.stage", "ingest.wait_staged", "ingest.apply",
        "ingest.block", "query.snapshot", "query.dp_release"))
    monkeypatch.setattr(telemetry, "recorded_spans",
                        lambda: telemetry.SpanLog(full, 3))
    assert read(traced) is None


def test_span_arithmetic_of_a_hand_made_log(monkeypatch):
    from repro.service import telemetry

    log = (
        _rec("ingest.wait_staged", 0, 0, 2), _rec("ingest.apply", 0, 2, 12),
        _rec("ingest.block", 0, 3, 12),
        _rec("ingest.wait_staged", 1, 12, 13), _rec("ingest.apply", 1, 14, 24),
        _rec("ingest.block", 1, 16, 24),
        # Key 3 does not follow key 1: no gap is counted between them.
        _rec("ingest.block", 3, 40, 50),
        _rec("ingest.stage", 0, 0, 5, "prefetch_to_device"),
        _rec("ingest.stage", 1, 5, 12, "prefetch_to_device"),
        _rec("ingest.stage", 2, 12, 21, "prefetch_to_device"),
        _rec("query.snapshot", 0, 1, 4, "reader-0"),
        _rec("query.dp_release", 0, 4, 24, "reader-0"))
    monkeypatch.setattr(telemetry, "recorded_spans",
                        lambda: telemetry.SpanLog(log, 0))
    run = SimpleNamespace(trace=object())
    read = {m: harness.load_module("metrics", m).read(run)
            for m in SPAN_METRICS}
    assert read == {"stage_ms": 7.0, "wait_staged_ms": 1.5,
                    "host_gap_ms": 4.0, "snapshot_ms": 3.0,
                    "dp_release_ms": 20.0}
    assert spans.gaps_ms(log, "ingest.block") == [4.0]


def test_named_kernel_op_still_reads_as_the_dense_kernel():
    """The dense kernel's op is named by its `pallas_call` name now
    (`%frugal_2u_dma.<n> = ... custom-call(...)`): the recorded trace with
    its kernel ops so renamed reads the same `dense_kernel_ms`."""
    path = os.path.join(harness.BENCH_DIR, "fixtures",
                        "trace-backfill-v5e.json")
    with open(path) as f:
        text = f.read()
    rec = json.loads(text.replace("%_blocked_jit.", "%frugal_2u_dma."))
    planes = [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=ln["name"], events=[
            SimpleNamespace(**ev) for ev in ln["events"]])
        for ln in p["lines"]]) for p in rec["planes"]]
    renamed = tr.reduce_planes(planes, rec["window_s"])
    kern = [ev for ev in renamed.ops[0] if ev[0].startswith("%frugal_2u_dma.")]
    assert len(kern) == 3 and len(renamed.kernels[0]) == 3
    want_ms = sum(e - s for _, s, e in kern) / 3 / 1e6
    assert tr.kernel_mean_ms(renamed, tr.INGEST_MODULE) == \
        pytest.approx(want_ms)
