#!/usr/bin/env python3
"""Smoke run of the served paths on a TPU, at deployment size.

    python chip_smoke.py [--seed N]            # one chip: dense, families, sparse
    python chip_smoke.py --chips 4 [--seed N]  # v5e 2x2 host: topologies only

One process drives every phase. Each phase prints one JSON line (smoke
timing, compile seconds, peak device bytes, JAX version and its checks);
any failed check raises and the run exits non-zero. The last line of a
passing run is ``{"ok": true, "device": {...}}``. Without a TPU the script
prints no result and exits 2.

Phases (one chip):
  dense     the paper's §7 GROUPBY deployment: 2^22 groups x (p50, p99) of
            lognormal flow sizes, 2U lanes, chunks of 64 ticks, ingested by
            a StreamingService with live snapshot reads from a trusted and
            an epsilon-DP tenant; sampled lanes are checked bit-exact against
            the jnp scan on the host CPU backend, the DP release against a
            replay.
  families  one frugal_update_auto chunk at 2^22 lanes per registered lane
            program, with the same sampled-lane check.
  sparse    an SLOFleet of ~1.57M (route x metric) lanes fed Zipf(0.99)
            routed events through observe()/flush(), p50/p99 reads, and a
            bit-exact replay of the same events on the host CPU backend.
Phase (--chips 4):
  topologies  the dense stream under TopologySpec(lanes=4) and
            TopologySpec(data=2, lanes=2), in shard_map mode, against the
            same stream on one device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# The references run on the host CPU backend next to the accelerator.
_plats = os.environ.get("JAX_PLATFORMS", "")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import FleetSpec, QuantileFleet  # noqa: E402
from repro.configs.platform import enable_compile_cache  # noqa: E402
from repro.core import frugal, streaming  # noqa: E402
from repro.core import program as program_mod  # noqa: E402
from repro.data.streams import flow_size_chunks, zipf_keys  # noqa: E402
from repro.kernels import frugal_update_auto  # noqa: E402
from repro.parallel import TopologySpec, merge_replica_planes  # noqa: E402
from repro.serve import SLOFleet  # noqa: E402
from repro.service import Snapshot, StreamingService, TenantPolicy  # noqa: E402

QUANTILES = (0.5, 0.99)


# ------------------------------------------------------------------ helpers
class CompileClock:
    """Seconds XLA spent compiling, summed from JAX's monitoring events."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if "backend_compile" in event:
            self.total += duration


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name, fn, clock: CompileClock, **kw) -> dict:
    """Run one phase and print its line; errors propagate."""
    c0, t0 = clock.total, time.perf_counter()
    out = fn(**kw)
    line = {"phase": name,
            "smoke_timing_wall_s": time.perf_counter() - t0,
            "compile_s": clock.total - c0,
            "peak_bytes_in_use": _peak_bytes(),
            "jax": jax.__version__, **out}
    print(json.dumps(line), flush=True)
    return line


def assert_bit_exact(what, got, want):
    """Bit-for-bit equality of two arrays (NaNs compare by their bits)."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"{what}: {got.shape}/{got.dtype} != "
                             f"{want.shape}/{want.dtype}")
    view = np.uint32 if got.dtype.itemsize == 4 else np.uint8
    bad = int(np.count_nonzero(got.view(view) != want.view(view)))
    if bad:
        raise AssertionError(f"{what}: {bad} of {got.size} elements differ")


def sample_blocks(rng, num_lanes, blocks, width):
    """Seeded lane sample: `blocks` distinct [start, start + width) runs."""
    starts = rng.choice(num_lanes // width, size=blocks, replace=False)
    return sorted(int(s) * width for s in starts)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("lanes_per_group",))
def _scan(program, planes, items, seed, quantile, t_offset, g_offset, *,
          lanes_per_group):
    out, _ = frugal.program_process_seeded(
        program, planes, items, seed, quantile, t_offset=t_offset,
        g_offset=g_offset, lanes_per_group=lanes_per_group)
    return out


def cpu_scan(program, planes, items, seed, quantile, t_offset, g_offset,
             lanes_per_group=1):
    """The jnp scan over a lane block, run on the host CPU backend."""
    cpu = jax.devices("cpu")[0]

    def put(x, dt):
        return jax.device_put(np.asarray(x, dt), cpu)

    out = _scan(program, tuple(put(p, np.float32) for p in planes),
                put(items, np.float32), put(seed, np.int32),
                put(quantile, np.float32), put(t_offset, np.int32),
                put(g_offset, np.int32), lanes_per_group=lanes_per_group)
    return tuple(np.asarray(p) for p in out)


def check_lane_blocks(what, program, got_planes, init_planes, quantile,
                      items_of, seed, t_offset, starts, width, q=1):
    """Every plane of each sampled block, read at its absolute lane ids,
    against the CPU scan from the same initial state and items."""
    for s in starts:
        block = slice(s, s + width)
        want = cpu_scan(program, [np.asarray(p[block]) for p in init_planes],
                        items_of(s // q, (s + width) // q), seed,
                        np.asarray(quantile[block]), t_offset, s,
                        lanes_per_group=q)
        for f, g, w in zip(program.layout.plane_fields, got_planes, want):
            assert_bit_exact(f"{what} plane {f} lanes [{s}, {s + width})",
                             np.asarray(g[block]), w)


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# ------------------------------------------------------------------- phases
def phase_dense(groups=2 ** 22, chunks=8, chunk_t=64, seed=0, blocks=16,
                width=256, epsilon=0.5, require_kernel=True) -> dict:
    """Dense flow-size chunks through StreamingService with live reads."""
    rng = np.random.default_rng(seed)
    data = list(flow_size_chunks(groups, chunks, chunk_t, rng))
    spec = FleetSpec(num_groups=groups, quantiles=QUANTILES,
                     chunk_t=chunk_t, program="2u")
    fleet0 = QuantileFleet.create(spec, seed=seed)
    init = tuple(np.asarray(p) for p in fleet0.state.planes())
    quantile = np.asarray(fleet0.state.quantile)

    text = compiled_text(lambda fl, x: fl.ingest(x), fleet0, data[0])
    has_kernel = "tpu_custom_call" in text
    if require_kernel and not has_kernel:
        raise AssertionError("dense ingest compiled without tpu_custom_call")

    svc = StreamingService(spec, seed=seed,
                           tenants=[TenantPolicy("partner", epsilon=epsilon)])
    t0 = time.perf_counter()
    svc.start(iter(data))
    live, cursors = 0, []
    while svc.ingest_running:
        cursors.append(svc.snapshot().items_ingested)
        svc.query("internal", quantile=0.99)
        if live % 4 == 0:   # the DP release costs a host pass over L
            svc.query("partner", quantile=0.99)
        live += 1
    svc.join()
    ingest_s = time.perf_counter() - t0
    if cursors != sorted(cursors) or any(c % chunk_t for c in cursors):
        raise AssertionError(f"live snapshot cursors out of order: {cursors}")

    final = svc.snapshot()
    if final.items_ingested != chunks * chunk_t:
        raise AssertionError(f"cursor {final.items_ingested} after "
                             f"{chunks * chunk_t} ticks")
    dp = svc.query("partner")
    raw = svc.query("internal")
    if raw.shape != (groups, len(QUANTILES)) or not np.isfinite(raw).all():
        raise AssertionError("trusted read is not finite [G, Q]")

    got = svc.fleet.state.planes()
    check_lane_blocks(
        "dense", spec.program, got, init, quantile,
        lambda g0, g1: np.concatenate([c[:, g0:g1] for c in data]),
        seed, 0, sample_blocks(rng, spec.num_lanes, blocks, width), width,
        q=len(QUANTILES))

    replay = QuantileFleet.create(spec, seed=seed)
    for c in data:
        replay = replay.ingest(c)
    for f, a, b in zip(spec.program.layout.plane_fields, got,
                       replay.state.planes()):
        assert_bit_exact(f"dense replay plane {f}", np.asarray(a),
                         np.asarray(b))
    assert_bit_exact("dp release replay", dp,
                     Snapshot.capture(replay).estimate_dp(epsilon))
    return {"groups": groups, "lanes": spec.num_lanes, "chunks": chunks,
            "chunk_t": chunk_t, "tpu_custom_call": has_kernel,
            "ingest_s": ingest_s, "live_reads": live,
            "lanes_checked": blocks * width, "dp_replay_bit_exact": True,
            "median_p50": float(np.median(raw[:, 0])),
            "median_p99": float(np.median(raw[:, 1]))}


def phase_families(groups=2 ** 22, chunk_t=64, seed=0, blocks=16,
                   width=256, require_kernel=True) -> dict:
    """One frugal_update_auto chunk per registered lane program."""
    rng = np.random.default_rng(seed + 1)
    items = next(flow_size_chunks(groups, 1, chunk_t, rng))
    quantile = rng.choice(np.float32([0.5, 0.9, 0.99]), groups)
    m0 = items[0].copy()
    # a window epoch boundary falls inside the chunk
    t_offset = 5 * 4096 - chunk_t // 2
    items_d = jnp.asarray(items)
    q_d = jnp.asarray(quantile)
    checked = {}
    for family in program_mod.registered_families():
        prog = program_mod.make_program(family)
        layout = prog.layout
        init = tuple(m0 if f in layout.heads else np.ones_like(m0)
                     for f in layout.plane_fields)
        planes_d = tuple(jnp.asarray(p) for p in init)
        fn = functools.partial(frugal_update_auto, seed=seed, program=prog,
                               t_offset=t_offset)
        compiled = jax.jit(fn).lower(items_d, planes_d, q_d).compile()
        has_kernel = "tpu_custom_call" in compiled.as_text()
        if require_kernel and not has_kernel:
            raise AssertionError(f"{family}: no tpu_custom_call")
        out = compiled(items_d, planes_d, q_d)
        check_lane_blocks(family, prog, out, init, quantile,
                          lambda g0, g1: items[:, g0:g1], seed, t_offset,
                          sample_blocks(rng, groups, blocks, width), width)
        checked[family] = {"tpu_custom_call": has_kernel, "bit_exact": True}
    return {"groups": groups, "chunk_t": chunk_t,
            "lanes_checked": blocks * width, "families": checked}


def _slo_run(capacity, routes, batches, seed):
    fleet = SLOFleet(seed=seed, capacity=capacity)
    fleet.ensure_routes(routes)
    names = [m for m, _ in fleet.metrics]
    for route_ids, metric_ids, values in batches:
        for r, m, v in zip(route_ids, metric_ids, values):
            fleet.observe(routes[r], names[m], float(v))
        fleet.flush()
    jax.block_until_ready(fleet._ticks)
    return fleet


def phase_sparse(capacity=524_288, n_routes=400_000, flushes=4,
                 events=16_384, zipf_s=0.99, seed=0, read_routes=16) -> dict:
    """Zipf-routed SLO events through SLOFleet.observe()/flush()."""
    rng = np.random.default_rng(seed + 2)
    routes = [f"t{i % 64}/ep-{i}" for i in range(n_routes)]
    batches = [(zipf_keys(n_routes, events, zipf_s, rng),
                rng.integers(0, 3, events),
                rng.lognormal(3.0, 0.5, events)) for _ in range(flushes)]
    t0 = time.perf_counter()
    fleet = _slo_run(capacity, routes, batches, seed)
    ingest_s = time.perf_counter() - t0

    hot = [routes[r] for r in range(read_routes)]
    reads = {r: fleet.summary(r) for r in hot}
    for r, s in reads.items():
        if not all(np.isfinite(v) for v in s.values()):
            raise AssertionError(f"non-finite SLO read for {r}: {s}")

    f = fleet._fleet
    k = 256
    lanes = jnp.arange(k, dtype=jnp.int32)
    text = compiled_text(lambda fl, l, v: fl.tick_lanes_sparse(l, v), f,
                         lanes, jnp.ones((k,), jnp.float32))

    with jax.default_device(jax.devices("cpu")[0]):
        ref = _slo_run(capacity, routes, batches, seed)
        ref_state = {n: np.asarray(getattr(ref, n))
                     for n in ("_m", "_step", "_sign", "_ticks")}
    for n, want in ref_state.items():
        assert_bit_exact(f"sparse {n}", np.asarray(getattr(fleet, n)), want)
    return {"lanes": int(fleet._m.shape[0]), "routes": n_routes,
            "flushes": flushes, "events_per_flush": events,
            "zipf_s": zipf_s, "ingest_s": ingest_s,
            "xla_scatter": "scatter(" in text,
            "tpu_custom_call": "tpu_custom_call" in text,
            "cpu_replay_bit_exact": True,
            "hot_route_p99_ttft": reads[hot[0]]["ttft_q99_ms"],
            "hot_route_p50_tok": reads[hot[0]]["tok_q50_ms"]}


def phase_topologies(groups=2 ** 22, chunks=8, chunk_t=64, seed=0) -> dict:
    """The dense stream on lanes=4 and data=2 x lanes=2 meshes against the
    same stream on one device."""
    n = len(jax.devices())
    if n < 4:
        raise AssertionError(f"topologies need 4 devices, found {n}")
    rng = np.random.default_rng(seed)
    data = list(flow_size_chunks(groups, chunks, chunk_t, rng))
    q = len(QUANTILES)

    def spec(topology=None):
        return FleetSpec(num_groups=groups, quantiles=QUANTILES,
                         chunk_t=chunk_t, program="2u", topology=topology)

    one = QuantileFleet.create(spec(), seed=seed)
    init = one.state
    for c in data:
        one = one.ingest(c)
    want = tuple(np.asarray(p) for p in one.state.planes())
    fields = one.spec.program.layout.plane_fields
    out = {"groups": groups, "chunks": chunks, "chunk_t": chunk_t}

    lanes4 = QuantileFleet.create(spec(TopologySpec(lanes=4)), seed=seed)
    for c in data:
        lanes4 = lanes4.ingest(c)
    devs = lanes4.state.sketch.m.sharding.device_set
    if len(devs) != 4:
        raise AssertionError(f"lanes=4 fleet on {len(devs)} device(s)")
    assert_bit_exact("lanes=4 estimates", lanes4.estimate(), one.estimate())
    for f, a, b in zip(fields, lanes4.sync()._lane_sketch().planes(), want):
        assert_bit_exact(f"lanes=4 plane {f}", np.asarray(a), b)
    out["lanes4"] = {"devices": len(devs), "bit_exact": True}

    mesh = QuantileFleet.create(spec(TopologySpec(data=2, lanes=2)),
                                seed=seed)
    if mesh.state.mode != "shard_map":
        raise AssertionError(f"data=2 x lanes=2 ran in {mesh.state.mode}")
    for c in data:
        mesh = mesh.ingest(c)
    # Replica r holds the chunks c with c % 2 == r, each at its absolute
    # tick: the one-device reference ingests those sub-streams and folds
    # them through the pinned merge rule.
    replicas = []
    for r in range(2):
        sk = init
        for i in range(r, chunks, 2):
            sk = streaming.ingest_array(
                sk, data[i], seed=seed, chunk_t=chunk_t,
                t_offset=i * chunk_t, lanes_per_group=q)
        replicas.append(tuple(np.asarray(p) for p in sk.planes()))
    merged = merge_replica_planes(
        one.spec.program, tuple(np.stack(ps) for ps in zip(*replicas)))
    est = mesh.estimate()
    synced = mesh.sync()
    assert_bit_exact("data=2 estimates after sync", synced.estimate(), est)
    for f, a, b in zip(fields, synced.state.replica_planes(), merged):
        for r in range(2):
            assert_bit_exact(f"data=2 replica {r} plane {f}", a[r], b)
    assert_bit_exact("data=2 estimates", est,
                     np.asarray(merged[0]).reshape(groups, q))
    out["data2_lanes2"] = {"mode": mesh.state.mode, "bit_exact": True}
    return out


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    clock = CompileClock()
    if args.chips == 4:
        run_phase("topologies", phase_topologies, clock, seed=args.seed)
    else:
        for name, fn in (("dense", phase_dense), ("families", phase_families),
                         ("sparse", phase_sparse)):
            run_phase(name, fn, clock, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
