"""Fixtures for the benchmark's own CPU tests: each cell at a tiny size."""
import copy
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.join(os.path.dirname(HERE), "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


def tiny(workload: str):
    """(bench, cell, config, traffic) of `workload`, cut to run on a CPU in
    a few seconds: 4,096 groups."""
    from yardstick import harness

    bench = harness.load_benchmark()
    cell, config, traffic = harness.find_cell(bench, workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["num_groups"] = 4096
    return bench, cell, config, traffic


@pytest.fixture
def run_tiny(capsys):
    """Run a tiny cell on the CPU devices, past the harness's chip gate."""
    import jax

    from yardstick import harness

    def go(workload, seed=2 ** 31 + 7, control=None, traced=False):
        bench, cell, config, traffic = tiny(workload)
        # A backlog on a CPU applies hundreds of tiny chunks a second, and
        # the reference replays every one.
        return harness.run_cell(bench, cell, config, traffic, seed=seed,
                                seconds=0.3, traced=traced,
                                devices=jax.devices(),
                                t_start=time.perf_counter(), control=control)

    return go
