"""The benchmark's parts, each alone: bytes, peaks, reference, generator,
discovery, the refusal without a TPU, and BENCHMARK.json's shape."""
import json
import os
import re
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from yardstick import harness, peaks, reference, roofline, traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def test_dense_chunk_bytes_of_groupby_config():
    cfg = harness.load_json(os.path.join(HERE, "configs",
                                         "groupby-2u-4m.json"))
    nbytes = roofline.dense_chunk_bytes(cfg["chunk_t"], cfg["num_groups"],
                                        len(cfg["quantiles"]),
                                        cfg["state_words"])
    assert nbytes == 1_207_959_552
    bound = roofline.bytes_bound_s(nbytes, peaks.peaks_for(
        "TPU v5 lite")["hbm_bytes_per_s"])
    assert bound == pytest.approx(1.475e-3, rel=1e-3)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_reference_uniform_is_the_counter_hash():
    from repro.core import rng as crng

    lanes = np.arange(0, 1 << 20, 4093, dtype=np.int64)
    for seed, t in ((0, 0), (-123456789, 77), (2 ** 31 - 1, 2 ** 31 + 5)):
        want = np.asarray(crng.counter_uniform(
            seed, crng.wrap_i32(t), lanes.astype(np.int32)))
        got = reference.uniform(seed, t, lanes)
        assert got.tobytes() == want.tobytes()


def test_reference_step_is_algorithm_3():
    from repro.core.reference import frugal2u_scalar

    rng = np.random.default_rng(3)
    xs = rng.lognormal(5.0, 1.0, 500).astype(np.float32)
    us = rng.random(500).astype(np.float32)
    m, step, sign = reference.fresh_lanes(1)
    for x, u in zip(xs, us):
        m, step, sign = reference.frugal2u_step(m, step, sign, x, u,
                                                np.float32(0.9))
    assert float(m[0]) == frugal2u_scalar(xs.tolist(), us.tolist(), 0.9)


def test_generator_is_seeded_and_same_count_for_every_seed():
    a = list(traffic.flow_size_chunks(64, 2, 8, traffic.rng_for(2 ** 33, 0)))
    b = list(traffic.flow_size_chunks(64, 2, 8, traffic.rng_for(2 ** 33, 0)))
    c = list(traffic.flow_size_chunks(64, 2, 8, traffic.rng_for(5, 0)))
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    assert a[0].tobytes() != c[0].tobytes()
    assert traffic.program_seed(2 ** 31) == -2 ** 31
    assert traffic.program_seed(2 ** 32 + 5) == 5


def test_release_schedule_does_not_slip_when_the_consumer_stalls():
    due = np.arange(40) * 0.01
    t0 = time.perf_counter()
    rel = traffic.Releaser(due, t0).start()
    got = []

    def consumer():
        for i in range(40):
            if not rel.wait_for(i):
                return
            got.append(time.perf_counter() - t0)
            if i == 5:
                time.sleep(0.2)           # the system stalls

    th = threading.Thread(target=consumer)
    th.start()
    th.join(10)
    rel.join()
    assert not th.is_alive() and len(got) == 40
    # After the stall the backlog is released at once, on schedule: the
    # units due during the stall are taken late, the schedule is not.
    assert rel.released == 40
    assert max(rel.lag_s) < 0.1
    assert got[6] > due[6] + 0.1


def test_a_backlog_is_all_due_at_once_and_never_runs_dry():
    due = harness.load_module("arrivals", "backlog").due({}, 51.0)
    assert len(due) >= 1 << 16 and not np.any(due)


def test_a_new_cell_config_and_metric_are_found_by_name(tmp_path):
    for kind in ("configs", "traffic", "metrics", "systems"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "echo-1k.json").write_text(json.dumps(
        {"system": "echo", "n": 1000}))
    (tmp_path / "traffic" / "steady.json").write_text(json.dumps(
        {"per_s": 5.0}))
    (tmp_path / "metrics" / "echo_per_s.py").write_text(
        "def read(run):\n    return run.events_visible / run.window_s\n")
    (tmp_path / "systems" / "echo.py").write_text(
        "class System:\n"
        "    def __init__(self, config, traffic, *, seed, seconds, run):\n"
        "        self.c, self.t, self.run = config, traffic, run\n"
        "    def setup(self): pass\n"
        "    def window(self, capture):\n"
        "        self.run.window_s = 2.0\n"
        "        self.run.events_visible = self.c['n'] * self.t['per_s']\n"
        "    def info(self): return {}\n"
        "    def release(self): pass\n"
        "    def check(self, checks, control=None):\n"
        "        checks.add('echo_wrong', 0, 0)\n")
    bench = {"workloads": [{"name": "echo", "config": "echo-1k",
                            "traffic": "steady", "chips": 1}],
             "end_to_end": [{"name": "echo_per_s", "unit": "1/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    (tmp_path / "arrivals").mkdir()
    (tmp_path / "arrivals" / "every2.py").write_text(
        "def due(traffic, seconds):\n"
        "    return [2.0 * k for k in range(int(seconds // 2))]\n")
    assert harness.load_module("arrivals", "every2", base=str(tmp_path)).due(
        {}, 6.0) == [0.0, 2.0, 4.0]
    cell, config, tr = harness.find_cell(bench, "echo", base=str(tmp_path))
    res = harness.run_cell(bench, cell, config, tr, seed=1, seconds=1.0,
                           traced=False, devices=jax.devices(),
                           t_start=time.perf_counter(), base=str(tmp_path))
    assert res["correct"] is True
    assert res["metrics"]["echo_per_s"]["value"] == 2500.0


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "groupby-backfill", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 2
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_its_shape():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(
            os.path.join(harness.ROOT, c["file"]))
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(HERE, "traffic",
                                           f"{w['traffic']}.json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py"))
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", cells)
    for cell in cells:
        reported = harness.metrics_of(bench, cell, traced=False)
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert harness.metrics_of(bench, cell, traced=True)
