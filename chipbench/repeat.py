#!/usr/bin/env python3
"""Run one cell several times, each run its own process, and summarise.

    python3 chipbench/repeat.py --workload <cell> --seeds 11,12,13 \
        --seconds 20 [--trace 0|1] [--control bf16] [--out runs.jsonl]

This process never imports JAX, so each child owns the chip. Per metric it
prints every value, the median and the spread: the distance between the
first and third quartile (`statistics.quantiles(values, n=4)`) as a share
of the median.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# A cell's first run in a checkout compiles; no run may take longer.
TIMEOUT_S = 1200.0


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--control", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    results = []
    for seed in args.seeds.split(","):
        cmd = [sys.executable, os.path.join(HERE, "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--seconds", args.seconds, "--trace", args.trace]
        if args.control:
            cmd += ["--control", args.control]
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=TIMEOUT_S)
        wall = time.perf_counter() - t0
        lines = proc.stdout.strip().splitlines()
        rec = {"seed": seed, "rc": proc.returncode, "wall_s": wall,
               "stderr_tail": proc.stderr[-1500:]}
        try:
            rec["result"] = json.loads(lines[-1])
            rec["info"] = json.loads(lines[-2])["info"]
        except (IndexError, ValueError, KeyError):
            rec["result"] = None
        results.append(rec)
        res = rec["result"] or {}
        print(json.dumps({"seed": seed, "rc": proc.returncode,
                          "wall_s": round(wall, 1),
                          "correct": res.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      res.get("metrics", {}).items()},
                          "checks": res.get("checks"),
                          "info": rec.get("info")}), flush=True)
        if rec["result"] is None:
            print(proc.stderr[-3000:], flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    names = sorted({k for r in results if r["result"]
                    for k in r["result"]["metrics"]})
    for name in names:
        vals = [r["result"]["metrics"][name]["value"] for r in results
                if r["result"] and name in r["result"]["metrics"]]
        print(json.dumps({"metric": name, "values": vals,
                          "median": statistics.median(vals),
                          "spread": spread(vals)}), flush=True)
    ok = all(r["result"] and r["result"]["correct"] for r in results)
    print(json.dumps({"workload": args.workload, "runs": len(results),
                      "all_correct": ok}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
