"""Each cell end to end on the CPU at a tiny size: the program agrees with
the plain reference, and the control (the reference in bfloat16 in the
program's place) comes out not correct."""
import pytest

from yardstick import harness

CELLS = ("groupby-backfill", "groupby-reads")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_correct_at_tiny_size(run_tiny, workload):
    res = run_tiny(workload)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert "setup_s" in res["metrics"] and "events_per_s" in res["metrics"]
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(run_tiny, workload):
    res = run_tiny(workload, control="bf16")
    assert res["correct"] is False
    assert res["checks"]["state_words_differing"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_busy_window_and_breakdown(run_tiny, workload):
    res = run_tiny(workload, traced=True)
    assert res["correct"], res["checks"]
    dev = res["device"]
    assert dev["window_s"] > 0 and dev["busy_s"] >= 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "setup_s" not in res["metrics"]
    # A CPU trace has no TPU device plane; the host-clock readers still read.
    bench = harness.load_benchmark()
    host = [m["name"] for m in harness.metrics_of(bench, workload, True)
            if m["source"] == "host_clock"]
    assert all(name in res["metrics"] for name in host), res["metrics"]
