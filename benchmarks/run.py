"""Benchmark harness — one module per paper table/figure (see DESIGN.md §8).

Prints ``name,us_per_call,derived`` CSV lines; full payloads land in
artifacts/bench/*.json. ``--full`` uses the paper's exact stream sizes
(minutes of CPU); default quick mode keeps CI-speed.
"""
import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale stream sizes (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. e1,e6")
    args = ap.parse_args()
    quick = not args.full

    from repro.configs.platform import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (
        bench_static_cauchy, bench_dynamic_cauchy, bench_groupby_tcp,
        bench_combined_stream, bench_groupby_twitter,
        bench_convergence_theory, bench_program_engine,
        bench_kernel_throughput, bench_sharded_fleet, bench_fleet_api,
        bench_drift_tracking, bench_resilience_overhead,
        bench_sparse_ingest, bench_service_e2e, bench_mesh2d,
        bench_roofline)

    suite = {
        "e1": ("static_cauchy (paper Fig 4)", bench_static_cauchy.run),
        "e2": ("dynamic_cauchy (paper Fig 5)", bench_dynamic_cauchy.run),
        "e3": ("groupby_tcp (paper Figs 6-7)", bench_groupby_tcp.run),
        "e4": ("combined_stream (paper Figs 8-9)", bench_combined_stream.run),
        "e5": ("groupby_twitter (paper Figs 10-11)", bench_groupby_twitter.run),
        "e6": ("theory Thm1/Thm2 (paper §4)", bench_convergence_theory.run),
        # e7 sat reserved for the paper's never-landed §7.4 frontier sweep
        # through PR 4; the lane-program engine claimed the gap: e7 now
        # gates the engine's dispatch overhead vs the PR-4 hand-specialized
        # paths (<= 1.05x, BENCH_program_engine.json).
        "e7": ("program_engine overhead (ours)", bench_program_engine.run),
        "e8": ("kernel_throughput (ours)", bench_kernel_throughput.run),
        "e9": ("sharded_fleet (ours)", bench_sharded_fleet.run),
        "e10": ("fleet_api overhead + Q-lanes (ours)", bench_fleet_api.run),
        "e11": ("drift_tracking decay vs vanilla (ours)",
                bench_drift_tracking.run),
        "e12": ("resilience overhead hardened vs bare (ours)",
                bench_resilience_overhead.run),
        "e13": ("sparse ingest flat-in-L + million-lane Zipf serve (ours)",
                bench_sparse_ingest.run),
        "e14": ("streaming service e2e ingest + live queries (ours)",
                bench_service_e2e.run),
        "e15": ("2-D mesh ingest vs 1-D + elastic reshard (ours)",
                bench_mesh2d.run),
        "e16": ("fraction-of-roofline for the compiled kernel (ours)",
                bench_roofline.run),
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - suite.keys()
        if unknown:  # a typo'd id must not silently run an empty suite
            ap.error(f"unknown benchmark id(s) {sorted(unknown)}; known: "
                     f"{', '.join(suite)}")

    print("name,us_per_call,derived")
    for key, (desc, fn) in suite.items():
        if only and key not in only:
            continue
        t0 = time.time()
        lines, _ = fn(quick=quick)
        for ln in lines:
            print(ln)
        print(f"# {key} [{desc}] done in {time.time() - t0:.1f}s",
              file=sys.stderr)


if __name__ == "__main__":
    main()
