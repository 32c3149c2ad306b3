"""The reduction from a profiler trace to per-layer numbers."""
import json
import os
from types import SimpleNamespace

import pytest

from yardstick import harness
from yardstick import trace as tr

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace-backfill-v5e.json")


def _fixture_trace():
    """The recorded TPU events as profiler planes, reduced."""
    with open(FIXTURE) as f:
        rec = json.load(f)
    planes = [SimpleNamespace(name=p["name"], lines=[
        SimpleNamespace(name=ln["name"], events=[
            SimpleNamespace(**ev) for ev in ln["events"]])
        for ln in p["lines"]]) for p in rec["planes"]]
    return rec, tr.reduce_planes(planes, rec["window_s"])


def test_recorded_tpu_trace_reduces_to_the_dense_kernel():
    rec, td = _fixture_trace()
    ops = rec["planes"][0]["lines"][1]["events"]
    # The dense kernel's op is named after the function that wraps the
    # pallas_call; its event name on a TPU is its HLO text.
    kern = [ev for ev in ops if ev["name"].startswith("%_blocked_jit.")]
    assert len(kern) == 3 and len(td.kernels[0]) == 3
    want_ms = sum(ev["end_ns"] - ev["start_ns"] for ev in kern) / 3 / 1e6
    assert tr.kernel_mean_ms(td, tr.INGEST_MODULE) == pytest.approx(want_ms)
    assert 0 < tr.busy_s(td) <= td.window_s
    run = SimpleNamespace(trace=td, device_kind="TPU v5 lite",
                          config=harness.load_json(os.path.join(
                              harness.BENCH_DIR, "configs",
                              "groupby-2u-4m.json")))
    read = {m: harness.load_module("metrics", m).read(run)
            for m in ("dense_kernel_ms", "dense_kernel_roofline",
                      "ingest_other_ms")}
    assert read["dense_kernel_ms"] == pytest.approx(want_ms)
    assert 0 < read["dense_kernel_roofline"] < 100
    ingest_ms = sum(e - s for n, s, e in td.modules[0]
                    if "ingest_array_scan" in n) / 3 / 1e6
    assert 0 < read["ingest_other_ms"] < ingest_ms - want_ms + 1e-6


def _td(ops, modules=(), spans=(), window_s=1.0, kernels=()):
    return tr.TraceData(window_s=window_s, ops={0: list(ops)},
                        modules={0: list(modules)}, spans=list(spans),
                        kernels={0: list(kernels)})


def test_busy_is_the_union_of_op_intervals():
    ops = [("a", 0.0, 10.0), ("b", 5.0, 20.0), ("c", 30.0, 40.0),
           ("d", 32.0, 35.0)]
    assert tr.union_ns([(s, e) for _, s, e in ops]) == 30.0
    assert tr.busy_s(_td(ops)) == pytest.approx(30e-9)


def test_kernel_time_is_custom_calls_inside_the_ingest_module():
    k1, k2 = ("dispatch.1", 10.0, 110.0), ("dispatch.1", 210.0, 330.0)
    stray = ("custom-call.7", 600.0, 700.0)    # outside the ingest module
    ops = [k1, ("fusion.1", 110.0, 130.0), k2, ("copy", 500.0, 510.0),
           stray]
    mods = [("jit__ingest_array_scan", 0.0, 140.0),
            ("jit__ingest_array_scan", 200.0, 340.0),
            ("jit_query_view", 590.0, 710.0)]
    td = _td(ops, mods, kernels=[k1, k2, stray])
    assert tr.kernel_mean_ms(td, tr.INGEST_MODULE) == pytest.approx(110e-6)
    inner = tr.inside(td.ops[0], tr.matching(mods, tr.INGEST_MODULE))
    assert [e[0] for e in inner] == ["dispatch.1", "fusion.1", "dispatch.1"]


class _Ev:
    def __init__(self, name, stats=()):
        self.name, self.stats = name, list(stats)


@pytest.mark.parametrize("ev,want", [
    (_Ev("custom-call.3"), True),
    (_Ev("_blocked_jit.2"), True),
    (_Ev("dispatch.1", [("long_name", "%dispatch.1 = (f32[1,8]) "
                         "custom-call(%a), custom_call_target=\"x\"")]),
     True),
    (_Ev("fusion.2", [("long_name", "%fusion.2 = f32[8] fusion(%a)"),
                      ("flops", 8)]), False),
    (_Ev("copy.1", [("long_name", "%copy.1 = f32[8] copy(%custom-call.3)")]),
     False),
    (_Ev("x.4", [("hlo_category", "custom-call")]), True),
    # On a TPU the op's event name is its HLO text.
    (_Ev("%_blocked_jit.2 = (f32[1,8388608]{1,0:T(1,128)}) custom-call(s32[3]"
         "{0} %pad_add_fusion, f32[64,8388608]{1,0:T(8,128)} %bitcast.4), "
         "custom_call_target=\"tpu_custom_call\""), True),
    (_Ev("%copy.1 = f32[64,8388608]{1,0:T(8,128)} copy(f32[64,8388608]"
         "{0,1:T(8,128)} %bitcast.4)"), False),
    (_Ev("%or_select_fusion = (f32[8388608]{0:T(1024)}) fusion(s32[8388608]"
         "{0:T(1024)S(1)} %bitcast.14), kind=kLoop"), False),
])
def test_custom_call_is_told_by_name_or_stat(ev, want):
    assert tr.is_custom_call(ev) is want


def test_idle_gaps_are_named_by_the_host_span_over_them():
    ops = [("x", 0.0, 10.0), ("y", 100.0, 110.0), ("z", 115.0, 120.0)]
    spans = [("generate", 20.0, 90.0), ("read", 109.0, 116.0)]
    gaps = tr.idle_gaps(_td(ops, spans=spans))
    assert gaps[0] == ["generate", pytest.approx(90e-9)]
    assert gaps[1] == ["read", pytest.approx(5e-9)]


def test_top_ops_sum_each_name():
    ops = [("k", 0.0, 5.0), ("k", 10.0, 15.0), ("j", 20.0, 21.0)]
    assert tr.top_ops(_td(ops)) == [["k", pytest.approx(10e-9)],
                                    ["j", pytest.approx(1e-9)]]
