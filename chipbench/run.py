#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are listed in
BENCHMARK.json at the repository root. Without a TPU, or with fewer chips
than the cell asks for, the run prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# The TPU runtime would otherwise write its logs to a fixed path in /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from yardstick import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
