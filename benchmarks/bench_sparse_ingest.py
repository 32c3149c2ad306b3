"""E13 (ours) — sparse event ingest is O(events), not O(lanes).

The bug this PR fixes: `tick_lanes_sparse` advertised "O(events) work
against millions of lanes" while materializing full [L] planes per round
(a broadcast quantile gather + one whole-plane copy per `.at[].set`).
The scatter path (kernels.ops.frugal_update_sparse, DESIGN.md §13) gathers
only the K event lanes, ticks them, scatters back in place (donated
buffers, on every platform).

Measured here, CPU/jnp donated path:

  * flat-in-L gate — a fixed 4096-event Zipf(1.2) round against L=2^16 vs
    L=2^22 total lanes (the acceptance pair). O(events) means per-round
    time is flat in L up to cache effects on the gathered rows; the gate
    is ratio <= 1.5x. The old O(L) path measures ~50-100x here.
  * bit-exactness — sparse rounds replay dense `tick_lanes` rounds
    bit-for-bit on EVERY registered LaneProgram family (hard assert: the
    speed claim is void if the trajectory differs).
  * serve scenario — a multi-tenant SLOFleet at ~1.5M lanes ingesting
    Zipf-routed events through observe()/flush(), reported as events/s.

Gate verdict lands in repo-root BENCH_sparse_ingest.json (`gate_met`;
loud warning on miss, benchmarks.check_gates enforces — wall-clock on a
shared runner is too noisy to hard-fail inside the bench).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.api import FleetSpec, QuantileFleet
from repro.core import program as program_mod
from repro.serve import SLOFleet
from .common import save_result, csv_line, write_bench_json

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(_ROOT, "BENCH_sparse_ingest.json")

EVENTS_PER_ROUND = 4096
GATE_L_SMALL = 16          # log2 — the acceptance pair
GATE_L_LARGE = 22
GATE_MAX_RATIO = 1.5
ZIPF_A = 1.2


def _zipf_round(rng: np.random.Generator, n_lanes: int, k: int) -> np.ndarray:
    """k DISTINCT Zipf(ZIPF_A) lane ids in [0, n_lanes), sorted — one
    round's event lanes. Distinct because a round may not repeat a lane
    (same-lane events split into successive rounds); sorted because the
    serve path's round builder emits runs in lane order."""
    seen = np.empty(0, np.int64)
    while seen.size < k:
        draw = (rng.zipf(ZIPF_A, size=4 * k) - 1) % n_lanes
        seen = np.union1d(seen, draw)          # sorts + dedups
    sel = rng.choice(seen, size=k, replace=False)
    sel.sort()
    return sel.astype(np.int32)


def _sparse_round_ms(log_l: int, reps: int, seed: int) -> float:
    """Median per-round wall time of the donated sparse path at L=2^log_l,
    fixed EVENTS_PER_ROUND Zipf events per round."""
    n_lanes = 1 << log_l
    spec = FleetSpec(num_groups=n_lanes, quantiles=(0.9,), backend="jnp")
    fleet = QuantileFleet.create(spec, seed=seed, per_lane_clock=True)
    rng = np.random.default_rng(seed)
    warm = 5
    batches = [(jnp.asarray(_zipf_round(rng, n_lanes, EVENTS_PER_ROUND)),
                jnp.asarray(rng.lognormal(3.0, 0.5, EVENTS_PER_ROUND)
                            .astype(np.float32)))
               for _ in range(reps + warm)]
    for lanes, vals in batches[:warm]:
        fleet = fleet.tick_lanes_sparse(lanes, vals, donate=True)
    jax.block_until_ready(fleet.state.m)
    times = []
    for lanes, vals in batches[warm:]:
        t0 = time.perf_counter()
        fleet = fleet.tick_lanes_sparse(lanes, vals, donate=True)
        jax.block_until_ready(fleet.state.m)
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def _dense_round_ms(log_l: int, reps: int, seed: int) -> float:
    """Reference: the O(L) dense `tick_lanes` round on the same events —
    what every sparse round used to cost in disguise."""
    n_lanes = 1 << log_l
    spec = FleetSpec(num_groups=n_lanes, quantiles=(0.9,), backend="jnp")
    fleet = QuantileFleet.create(spec, seed=seed, per_lane_clock=True)
    rng = np.random.default_rng(seed)
    items = np.full(n_lanes, np.nan, np.float32)
    lanes = _zipf_round(rng, n_lanes, EVENTS_PER_ROUND)
    items[lanes] = rng.lognormal(3.0, 0.5, EVENTS_PER_ROUND)
    items = jnp.asarray(items)
    fleet = fleet.tick_lanes(items)               # warm/compile
    jax.block_until_ready(fleet.state.m)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fleet = fleet.tick_lanes(items)
        jax.block_until_ready(fleet.state.m)
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def _assert_bit_exact_all_programs(seed: int) -> dict:
    """Sparse rounds must replay dense rounds bit-for-bit on every
    registered program family (estimates AND per-lane clocks)."""
    verdicts = {}
    for prog in program_mod.test_instances():
        spec = FleetSpec(num_groups=24, quantiles=(0.5, 0.9),
                         backend="jnp", program=prog)
        dense = QuantileFleet.create(spec, seed=seed, per_lane_clock=True)
        sparse = QuantileFleet.create(spec, seed=seed, per_lane_clock=True)
        n_lanes = spec.num_lanes
        rng = np.random.default_rng(seed + 1)
        for _ in range(4):
            k = int(rng.integers(1, n_lanes + 1))
            lanes = np.sort(rng.choice(n_lanes, k, replace=False)) \
                .astype(np.int32)
            vals = rng.lognormal(3.0, 0.5, k).astype(np.float32)
            items = np.full(n_lanes, np.nan, np.float32)
            items[lanes] = vals
            dense = dense.tick_lanes(items)
            sparse = sparse.tick_lanes_sparse(lanes, vals, donate=True)
        same = (np.array_equal(dense.estimate(), sparse.estimate())
                and np.array_equal(np.asarray(dense.cursor.t_offset),
                                   np.asarray(sparse.cursor.t_offset)))
        verdicts[prog.family] = bool(same)
        assert same, f"sparse diverges from dense for {prog.family}"
    return verdicts


def _slo_scenario(quick: bool, seed: int) -> dict:
    """Multi-tenant serve fleet at ~1.5M lanes: Zipf-routed events through
    the public observe()/flush() path (includes the vectorized round
    assignment + sparse donated rounds)."""
    n_routes = 100_000 if quick else 400_000
    n_flushes = 6 if quick else 12
    fleet = SLOFleet(seed=seed, capacity=524_288)   # x3 metrics: ~1.57M lanes
    fleet.ensure_routes(f"t{i % 64}/ep-{i}" for i in range(n_routes))
    rng = np.random.default_rng(seed)
    metrics = [m for m, _ in fleet.metrics]
    route_of = (rng.zipf(ZIPF_A, size=n_flushes * EVENTS_PER_ROUND) - 1) \
        % n_routes
    vals = rng.lognormal(3.0, 0.5, route_of.size)
    # warm one flush cycle (compile), then time the rest
    t_total, n_timed = 0.0, 0
    for f in range(n_flushes):
        sl = slice(f * EVENTS_PER_ROUND, (f + 1) * EVENTS_PER_ROUND)
        rts, vs = route_of[sl], vals[sl]
        t0 = time.perf_counter()
        for r, v, m in zip(rts, vs, rng.choice(metrics, EVENTS_PER_ROUND)):
            fleet.observe(f"t{r % 64}/ep-{r}", m, float(v))
        fleet.flush()
        jax.block_until_ready(fleet._ticks)
        dt = time.perf_counter() - t0
        if f > 0:
            t_total += dt
            n_timed += EVENTS_PER_ROUND
    return {
        "slo_num_lanes": fleet.num_lanes,
        "slo_num_routes": n_routes,
        "slo_events_per_s": n_timed / t_total,
        "slo_flush_ms_per_4096": t_total / (n_flushes - 1) * 1e3,
    }


def run(quick: bool = True, seed: int = 0):
    reps = 40 if quick else 100
    bit_exact = _assert_bit_exact_all_programs(seed)

    t_small = _sparse_round_ms(GATE_L_SMALL, reps, seed)
    t_large = _sparse_round_ms(GATE_L_LARGE, reps, seed)
    ratio = t_large / t_small
    gate_met = ratio <= GATE_MAX_RATIO
    # context: what the old O(L) path cost per round at the large L
    t_dense_large = _dense_round_ms(GATE_L_LARGE, max(3, reps // 10), seed)

    slo = _slo_scenario(quick, seed)

    payload = {
        "events_per_round": EVENTS_PER_ROUND,
        "zipf_a": ZIPF_A,
        "l_small": 1 << GATE_L_SMALL,
        "l_large": 1 << GATE_L_LARGE,
        "sparse_round_ms_l_small": t_small,
        "sparse_round_ms_l_large": t_large,
        "flat_in_l_ratio": ratio,
        "gate_max_ratio": GATE_MAX_RATIO,
        "gate_met": bool(gate_met),
        "dense_round_ms_l_large": t_dense_large,
        "sparse_speedup_vs_dense_l_large": t_dense_large / t_large,
        "bit_exact_vs_dense": bit_exact,
        **slo,
    }
    write_bench_json(BENCH_JSON, payload)
    save_result("e13_sparse_ingest", payload)

    if not gate_met:
        print(f"WARNING: sparse round at L=2^{GATE_L_LARGE} is "
              f"{ratio:.2f}x the L=2^{GATE_L_SMALL} time (gate "
              f"{GATE_MAX_RATIO}x) — see {BENCH_JSON}; re-check on an "
              "unloaded machine", flush=True)

    lines = [
        csv_line("sparse_round_l2pow16",
                 t_small * 1e3 / EVENTS_PER_ROUND,
                 f"round_ms={t_small:.3f}"),
        csv_line("sparse_round_l2pow22",
                 t_large * 1e3 / EVENTS_PER_ROUND,
                 f"round_ms={t_large:.3f};ratio={ratio:.2f}x;"
                 f"gate_met={gate_met}"),
        csv_line("sparse_vs_dense_l2pow22",
                 t_dense_large * 1e3 / EVENTS_PER_ROUND,
                 f"speedup={t_dense_large / t_large:.1f}x"),
        csv_line("slo_zipf_1p5M_lanes",
                 1e6 / slo["slo_events_per_s"],
                 f"events_per_s={slo['slo_events_per_s']:.0f}"),
    ]
    return lines, payload
