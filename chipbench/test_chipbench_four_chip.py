"""The four-chip cell `groupby-backfill-4chip` on four host CPU devices at a
tiny size (a child process, so that it runs when this process started JAX
with one device), its lane sample, and its four per-layer readers on a
hand-made four-device trace."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from yardstick import harness
from yardstick import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "groupby-backfill-4chip"
READERS = ("shard_kernel_ms", "shard_kernel_roofline", "shard_skew")

_CHILD = r"""
import io, json, sys, time
import jax
from conftest import tiny
from yardstick import harness

assert len(jax.devices()) == 4, jax.devices()
out = {}
for name, control, traced in (("plain", None, False), ("bf16", "bf16", False),
                              ("traced", None, True)):
    bench, cell, config, traffic = tiny(sys.argv[1])
    out[name] = harness.run_cell(
        bench, cell, config, traffic, seed=2 ** 33 + 5, seconds=0.3,
        traced=traced, devices=jax.devices(), t_start=time.perf_counter(),
        control=control, out=io.StringIO())
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    """The tiny cell run plain, with the bf16 control and traced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    src = os.path.join(os.path.dirname(HERE), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [HERE, src] + [p for p in [env.get("PYTHONPATH")] if p])
    proc = subprocess.run([sys.executable, "-c", _CHILD, CELL], cwd=HERE,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


def test_four_chip_cell_correct_at_tiny_size(runs):
    res = runs["plain"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"setup_s", "events_per_s"}
    assert list(res)[-1] == "checks"
    assert all(c["value"] == 0 for c in res["checks"].values())


def test_four_chip_control_is_not_correct(runs):
    res = runs["bf16"]
    assert res["correct"] is False
    assert res["checks"]["state_words_differing"]["value"] > 0


def test_four_chip_traced_run_is_correct_and_reads_no_device_plane(runs):
    """A CPU trace has no TPU device plane, so the cell's device-trace
    readers find nothing there, and the traced run still checks out."""
    res = runs["traced"]
    assert res["correct"], res["checks"]
    assert "setup_s" not in res["metrics"]
    for name in READERS:
        assert name not in res["metrics"]


def test_cell_config_and_entries():
    bench = harness.load_benchmark()
    cell, config, traffic = harness.find_cell(bench, CELL)
    assert cell["chips"] == 4 and cell["traffic"] == "backfill"
    assert config["system"] == "sharded_service"
    assert config["num_groups"] == 2 ** 24 and config["topology"] == {
        "lanes": 4}
    traced = [m["name"] for m in harness.metrics_of(bench, CELL, True)]
    assert traced == list(READERS)
    for m in bench["per_layer"]:
        assert (m["name"] in READERS) == (m["workloads"] == [CELL])


@pytest.mark.parametrize("lanes,seed", [(2 ** 25, 2 ** 33 + 5),
                                        (2 ** 25, 7), (8192, 2 ** 31 + 7)])
def test_sampled_lanes_cover_every_shard(lanes, seed):
    mod = harness.load_module("systems", "sharded_service")
    got = mod.sample_lanes(seed, lanes, 4)
    per_shard = np.bincount(got // (lanes // 4), minlength=4)
    want = mod.SHARD_BLOCKS * mod.SAMPLE_WIDTH
    assert per_shard.tolist() == [want] * 4
    assert len(np.unique(got)) == len(got) and got.max() < lanes
    assert np.array_equal(got, mod.sample_lanes(seed, lanes, 4))


def test_a_program_without_sharded_staging_stops_before_device_work(
        monkeypatch):
    from repro.api import QuantileFleet

    monkeypatch.delattr(QuantileFleet, "stage")
    mod = harness.load_module("systems", "sharded_service")
    bench = harness.load_benchmark()
    cell, config, traffic = harness.find_cell(bench, CELL)
    with pytest.raises(RuntimeError, match="QuantileFleet.stage"):
        mod.System(config, traffic, seed=1, seconds=1.0,
                   run=harness.Run(cell, config, traffic, 1.0))


def _ms(x):
    return x * 1e6


def _four_device_trace():
    """Two `jit_sharded_ingest` calls on each of four devices; the kernel
    takes 70 ms a call on devices 0-2 and 72 ms on device 3, and device 1
    runs one more 10 ms op outside the ingest."""
    ops, modules, kernels = {}, {}, {}
    for d in range(4):
        k = 72 if d == 3 else 70
        modules[d] = [("jit_sharded_ingest(11)", _ms(0), _ms(100)),
                      ("jit_sharded_ingest(11)", _ms(200), _ms(300))]
        kernels[d] = [("%frugal_2u_dma.2", _ms(10), _ms(10 + k)),
                      ("%frugal_2u_dma.2", _ms(210), _ms(210 + k))]
        ops[d] = kernels[d] + [("%broadcast_in_dim.5", _ms(0), _ms(10)),
                               ("%broadcast_in_dim.5", _ms(200), _ms(210))]
        if d == 1:
            ops[d].append(("%copy.3", _ms(400), _ms(410)))
    return tr.TraceData(window_s=0.5, ops=ops, modules=modules, spans=[],
                        kernels=kernels)


def _run(trace):
    bench = harness.load_benchmark()
    _, config, _ = harness.find_cell(bench, CELL)
    return SimpleNamespace(trace=trace, config=config,
                           device_kind="TPU v5 lite")


def test_device_trace_readers_on_a_four_device_trace():
    run = _run(_four_device_trace())
    read = {m: harness.load_module("metrics", m).read(run)
            for m in READERS}
    assert read["shard_kernel_ms"] == pytest.approx(70.5)
    # One chip's share of a chunk: [64, 2^22] items, 2^23 lanes of two
    # words read and written, at 819 GB/s.
    nbytes = 64 * 2 ** 22 * 4 + 2 * 2 ** 23 * 2 * 4
    assert read["shard_kernel_roofline"] == pytest.approx(
        100 * nbytes / 819e9 / 70.5e-3)
    busy = [160, 170, 160, 164]
    assert read["shard_skew"] == pytest.approx(
        100 * (max(busy) - min(busy)) / np.mean(busy))


def test_skew_needs_two_devices():
    td = _four_device_trace()
    td.ops = {0: td.ops[0]}
    assert harness.load_module("metrics", "shard_skew").read(_run(td)) is None


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_nothing_untraced(metric):
    read = harness.load_module("metrics", metric).read
    assert read(_run(None)) is None
