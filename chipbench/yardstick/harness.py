"""The benchmark's one run: find the cell's files, gate on the chip, set
up, measure, check, print.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in BENCHMARK.json:

    chipbench/configs/<config>.json   sizes, guarantees, the system to drive
    chipbench/traffic/<mix>.json      parameters the system's generator reads
    chipbench/arrivals/<kind>.py      due(traffic, seconds) -> due offsets
    chipbench/metrics/<metric>.py     read(run) -> number, or None
    chipbench/systems/<system>.py     System(config, traffic, seed=, seconds=,
                                      run=): setup, window, info, release,
                                      check
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from typing import Optional

import numpy as np

from . import checks as checks_mod
from . import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


# ----------------------------------------------------------------- discovery
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: str = BENCH_DIR):
    """<base>/<kind>/<name>.py as a fresh module."""
    path = os.path.join(base, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, workload: str, base: str = BENCH_DIR):
    """(cell, config, traffic) of a workload name."""
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            config = load_json(os.path.join(base, "configs",
                                            f"{cell['config']}.json"))
            traffic = load_json(os.path.join(base, "traffic",
                                             f"{cell['traffic']}.json"))
            return cell, config, traffic
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell_name: str, traced: bool) -> list:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


# ---------------------------------------------------------------- the device
def require_chips(chips: int):
    """The devices a cell runs on; NoChip without an accelerator."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices


def device_stamp(devices, cell_chips: int) -> dict:
    used = devices[:cell_chips]
    peaks = []
    for d in used:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


# Copied from chip_smoke.py (CompileClock), with counts.
class CompileClock:
    """Seconds spent compiling or loading programs, and compiles and
    persistent-cache loads counted, from JAX's monitoring events. JAX
    times a program found in the cache as a compile too, so `compiles`
    counts both and `cache_loads` the programs found."""

    def __init__(self):
        import jax

        self.total = 0.0
        self.compiles = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if "backend_compile" in event:
            self.total += duration
            self.compiles += 1
        elif "cache_retrieval" in event:
            self.cache_loads += 1

    def mark(self):
        return (self.total, self.compiles, self.cache_loads)


def set_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout;
    every compile is cached, however short."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------- the run
class Run:
    """What a system recorded in one window; metric readers read this."""

    def __init__(self, cell: dict, config: dict, traffic: dict,
                 seconds: float):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seconds = float(seconds)
        self.window_s: Optional[float] = None
        self.events_visible = 0
        self.read_ms: Optional[np.ndarray] = None
        self.host_spans_ms: dict = {}
        self.trace: Optional[trace_mod.TraceData] = None
        self.device_kind: Optional[str] = None
        self.attempted = 0
        self.failed = 0


def run_cell(bench: dict, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, traced: bool, devices,
             t_start: float, control: Optional[str] = None, out=None,
             base: str = BENCH_DIR) -> dict:
    """Set up, measure, check; returns the result object (also printed)."""
    clock = CompileClock()
    system_mod = load_module("systems", config["system"], base)
    run = Run(cell, config, traffic, seconds)
    run.device_kind = devices[0].device_kind
    system = system_mod.System(config, traffic, seed=seed, seconds=seconds,
                               run=run)
    system.setup()
    setup_s = time.perf_counter() - t_start
    before = clock.mark()
    capture = trace_mod.Capture() if traced else None
    system.window(capture)
    after = clock.mark()
    info = {"compiles_in_window": after[1] - before[1],
            "cache_loads_in_window": after[2] - before[2],
            "compile_s_in_window": after[0] - before[0],
            "compile_s_total": after[0],
            # A compile found in the persistent cache counts in both.
            "compiles_total": after[1], "cache_loads_total": after[2]}
    info.update(system.info())
    stamp = device_stamp(devices, cell["chips"])
    system.release()

    checks = checks_mod.Checks()
    system.check(checks, control=control)

    metrics = {}
    for m in metrics_of(bench, cell["name"], traced):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = load_module("metrics", m["name"], base).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": checks.correct, "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics,
              "device": stamp}
    if traced:
        td = run.trace
        result["device"]["busy_s"] = trace_mod.busy_s(td)
        result["device"]["window_s"] = td.window_s
        result["breakdown"] = {"device_ops": trace_mod.top_ops(td),
                               "idle_gaps": trace_mod.idle_gaps(td)}
    result["checks"] = checks.items
    out = sys.stdout if out is None else out
    print(json.dumps({"info": info}), file=out, flush=True)
    print(f"info {json.dumps(info)}", file=sys.stderr, flush=True)
    checks.print_stderr()
    print(json.dumps(result), file=out, flush=True)
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell on the chip and print its "
                    "result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bf16",), default=None,
                    help="put the reference, computed in bfloat16, in the "
                         "program's place (must come out not correct)")
    return ap.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    bench = load_benchmark()
    cell, config, traffic = find_cell(bench, args.workload)
    set_compile_cache()
    try:
        devices = require_chips(int(cell["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    run_cell(bench, cell, config, traffic, seed=args.seed,
             seconds=args.seconds, traced=bool(args.trace), devices=devices,
             t_start=t_start, control=args.control)
    return 0
