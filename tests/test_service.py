"""repro.service: snapshot consistency, pipeline put-ahead, chaos, telemetry.

The load-bearing guarantees (DESIGN.md §14):

  * snapshot consistency — a query interleaved with ingest at ANY chunk
    boundary answers bit-identically to a single-threaded replay of the
    same cursor, across jnp/fused/sharded and the `2u-dp` program (whose
    Laplace noise replays from (seed^salt, t_next, lane));
  * donation immunity    — a Snapshot owns real host copies, so
    tick_lanes_sparse(donate=True) rounds that overwrite the old device
    buffers in place never mutate an already-taken snapshot;
  * query_stall chaos    — a reader killed mid-capture leaves ingest
    untouched and the retried capture answers bit-identically;
  * put-ahead pipeline   — data.pipeline.prefetch_to_device overlaps the
    source draw with consumer compute (proven by event ordering, not
    wall-clock), yields bit-identical values, and relays source errors;
  * DP tenant gating     — untrusted tenants read only the noised release,
    deterministic at a cursor; unknown tenants read nothing;
  * spans                — recorded only inside a profiler capture, into
    the log and the profile's host plane, the newest capture's alone.
"""
import glob
import os
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.api import FleetSpec, QuantileFleet, TopologySpec
from repro.core import program as program_mod
from repro.core import rng as crng
from repro.core.program import make_program
from repro.data.pipeline import DataConfig, SyntheticCorpus, \
    prefetch_to_device
from repro.resilience import FaultPlan, QueryStalled, chaos
from repro.service import (IngestPipeline, Snapshot, StreamingService,
                           Telemetry, TenantPolicy, recorded_spans,
                           runtime_metadata, span, telemetry)

SEEDS = tuple(int(s) for s in os.environ.get("CHAOS_SEEDS", "0").split(","))

G, CHUNK_T, N_CHUNKS = 8, 16, 6
# "sharded"/"mesh2d" are PLACEMENT legs (spelled via TopologySpec below):
# 1-D lane mesh and the 2-D (data × lane) mesh whose replicas ingest
# disjoint chunk shards. On one device they degrade to single placement /
# the sequential replica loop; the multi-device CI job runs them for real.
BACKENDS = ("jnp", "fused", "sharded", "mesh2d")


def _chunks(seed=0, n=N_CHUNKS, t=CHUNK_T, g=G):
    rng = np.random.default_rng(seed)
    return [rng.normal(3.0, 2.0, size=(t, g)).astype(np.float32)
            for _ in range(n)]


def _spec(backend="fused", program=None, g=G, quantiles=(0.5, 0.9)):
    topo = None
    if backend in ("sharded", "mesh2d"):
        lanes = min(2, len(jax.devices()))
        topo = TopologySpec(data=2 if backend == "mesh2d" else 1,
                            lanes=lanes)
        backend = "fused"
    return FleetSpec(num_groups=g, quantiles=quantiles, backend=backend,
                     chunk_t=CHUNK_T, topology=topo,
                     program=program if program is not None else "2u")


# ------------------------------------------------------- snapshot consistency
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("program", ["2u", "2u-dp", "2u-window"])
def test_snapshot_at_every_boundary_matches_replay(backend, program):
    """Interleave ingest chunks and snapshot queries at EVERY chunk
    boundary; each answer must be bit-identical to a fresh single-threaded
    fleet replayed to the same cursor. Covers the plain head query, the
    window plane selection (t_next parity), and the 2u-dp noised release
    (noise a pure function of (seed^salt, t_next, lane))."""
    prog = make_program(program, window=24) if program == "2u-window" \
        else (make_program(program, epsilon=0.7) if program == "2u-dp"
              else program)
    spec = _spec(backend, program=prog)
    svc = StreamingService(spec, seed=11)
    chunks = _chunks(seed=2)
    answers = []
    for c in chunks:
        answers.append(svc.snapshot().estimate())       # pre-chunk boundary
        svc.ingest(c)
    answers.append(svc.snapshot().estimate())
    # single-threaded replay on the jnp backend (cross-backend agreement is
    # part of what this pins). The 2-D leg replays on ITS OWN placement:
    # replicas merge through the pinned rule, a deterministic but distinct
    # estimator from the single trajectory (DESIGN.md §15).
    ref_backend = "mesh2d" if backend == "mesh2d" else "jnp"
    ref = QuantileFleet.create(_spec(ref_backend, program=prog), seed=11)
    np.testing.assert_array_equal(answers[0], ref.estimate())
    for i, c in enumerate(chunks):
        ref = ref.ingest(c)
        np.testing.assert_array_equal(
            answers[i + 1], ref.estimate(),
            err_msg=f"boundary {i + 1} diverges from replay")


@pytest.mark.parametrize("chaos_seed", SEEDS)
def test_threaded_queries_under_ingest_match_replay(chaos_seed):
    """Concurrent mode: queries race the background ingest thread; every
    answer must still be exact at ITS cursor (the snapshot pins a published
    fleet version — there are no torn reads to be had)."""
    spec = _spec("fused", g=32)
    svc = StreamingService(spec, seed=chaos_seed)
    chunks = _chunks(seed=chaos_seed + 7, n=10, g=32)

    def slow():
        for c in chunks:
            time.sleep(0.001)
            yield c

    svc.start(slow())
    seen = {}
    while svc.ingest_running:
        s = svc.snapshot()
        seen[s.items_ingested] = s.estimate()
    svc.join()
    final = svc.snapshot()
    seen[final.items_ingested] = final.estimate()
    assert final.items_ingested == 10 * CHUNK_T

    ref = QuantileFleet.create(_spec("jnp", g=32), seed=chaos_seed)
    if 0 in seen:
        np.testing.assert_array_equal(seen[0], ref.estimate())
    done = 0
    for c in chunks:
        ref = ref.ingest(c)
        done += CHUNK_T
        if done in seen:
            np.testing.assert_array_equal(seen[done], ref.estimate(),
                                          err_msg=f"cursor {done}")


def test_snapshot_survives_donated_sparse_rounds():
    """The donation-aliasing bug class the ISSUE names: a snapshot captured
    BEFORE tick_lanes_sparse(donate=True) rounds must not change when the
    donated rounds overwrite the old device buffers in place."""
    spec = FleetSpec(num_groups=64, quantiles=(0.5,), backend="fused")
    fleet = QuantileFleet.create(spec, seed=5, per_lane_clock=True)
    rng = np.random.default_rng(0)
    fleet = fleet.tick_lanes(rng.normal(size=64).astype(np.float32))
    snap = Snapshot.capture(fleet)
    before = snap.estimate().copy()
    for _ in range(20):
        lanes = rng.choice(64, size=8, replace=False).astype(np.int32)
        vals = rng.normal(size=8).astype(np.float32)
        fleet = fleet.tick_lanes_sparse(lanes, vals, donate=True)
    np.testing.assert_array_equal(snap.estimate(), before)
    # and the planes themselves are host-owned numpy, not device aliases
    assert all(isinstance(p, np.ndarray) for p in snap.m_planes)


# ------------------------------------------------------------- chaos: stall
@pytest.mark.parametrize("chaos_seed", SEEDS)
def test_query_stall_leaves_ingest_unperturbed_and_retry_exact(chaos_seed):
    """Kill the reader mid-capture at a seeded query index: ingest's final
    state must equal the never-queried run bit-for-bit, and re-asking at
    the same cursor must answer identically to an unstalled service."""
    spec = _spec("fused")
    chunks = _chunks(seed=3)
    n_queries = N_CHUNKS + 1
    plan = FaultPlan.seeded_query_stall(chaos_seed, n_queries)

    svc = StreamingService(spec, seed=9)
    stalled_at = []
    with chaos.armed(plan):
        for i, c in enumerate(chunks):
            try:
                svc.query()
            except QueryStalled:
                stalled_at.append(i)
                got = svc.query()               # immediate retry
                clean = StreamingService(spec, seed=9)
                for cc in chunks[:i]:
                    clean.ingest(cc)
                np.testing.assert_array_equal(got, clean.query())
            svc.ingest(c)
    assert plan.fired() == 1 and len(stalled_at) == 1
    assert svc.stats()["counters"]["queries_stalled"] == 1

    ref = QuantileFleet.create(spec, seed=9)
    for c in chunks:
        ref = ref.ingest(c)
    np.testing.assert_array_equal(svc.snapshot().estimate(), ref.estimate())


def test_query_stall_fires_inside_threaded_service():
    """The stall hook also fires on the concurrent path and is counted."""
    svc = StreamingService(_spec("fused"), seed=1)
    svc.ingest(_chunks(n=1)[0])
    with chaos.armed(FaultPlan.query_stall(at=1)):
        with pytest.raises(QueryStalled):
            svc.query()
        after = svc.query()
    np.testing.assert_array_equal(after, svc.query())
    assert svc.stats()["counters"]["queries_stalled"] == 1


# --------------------------------------------------------------- DP tenants
def test_tenant_gating_trusted_vs_dp_vs_unknown():
    svc = StreamingService(_spec("fused"), seed=4,
                           tenants=[TenantPolicy("partner", epsilon=0.5)])
    for c in _chunks(seed=5, n=3):
        svc.ingest(c)
    raw = svc.query()                           # internal = trusted
    noised = svc.query(tenant="partner")
    assert raw.shape == noised.shape
    assert not np.array_equal(raw, noised)      # the release IS perturbed
    # deterministic at a cursor: same snapshot, same tenant, same answer
    np.testing.assert_array_equal(noised, svc.query(tenant="partner"))
    # ...and replayable offline through the same 2u-dp query
    snap = svc.snapshot()
    np.testing.assert_array_equal(noised, snap.estimate_dp(0.5))
    with pytest.raises(KeyError):
        svc.query(tenant="nobody")
    with pytest.raises(ValueError, match="epsilon"):
        TenantPolicy("bad", epsilon=0.0)


def test_dp_program_fleet_is_not_double_noised():
    """A fleet already running 2u-dp releases through its OWN calibrated
    noise for every tenant — estimate_dp must not stack a second draw."""
    prog = make_program("2u-dp", epsilon=1.0)
    svc = StreamingService(_spec("fused", program=prog), seed=2,
                           tenants=[TenantPolicy("ext", epsilon=1.0)])
    svc.ingest(_chunks(n=1)[0])
    np.testing.assert_array_equal(svc.query(), svc.query(tenant="ext"))


def _device_dp_release(m, epsilon, seed, t_next, lanes):
    """The Laplace release with its uniforms hashed by the device kernel's
    `counter_uniform`: the bits the host release must keep."""
    u = np.asarray(crng.counter_uniform(
        crng.wrap_i32(int(seed) ^ program_mod._DP_SALT),
        jnp.asarray(t_next, jnp.int32), jnp.asarray(lanes, jnp.int32)),
        np.float64)
    c = u - 0.5
    noise = -(1.0 / epsilon) * np.sign(c) * np.log(
        np.maximum(1.0 - 2.0 * np.abs(c), np.finfo(np.float64).tiny))
    return (np.asarray(m, np.float64) + noise).astype(np.float32)


def test_dp_release_makes_no_transfer_and_keeps_its_bits():
    """The DP release runs on host data alone: under a transfer guard that
    refuses every host<->device copy, `_query_dp` and
    `Snapshot.estimate_dp` answer in numpy, bit-equal to the release whose
    uniforms the device hashed — across the release's block boundaries,
    on a scalar and a per-lane clock."""
    eps, seed, n = 0.5, 2 ** 31 - 3, 200_003     # several release blocks
    m = np.random.default_rng(7).normal(size=n).astype(np.float32)
    lanes = 2 ** 31 - 2048 + np.arange(n, dtype=np.int64)
    clocks = (np.int32(1_000_003),
              (np.arange(n) * 7 + 2 ** 31 - 5 * n).astype(np.int32))
    want = [_device_dp_release(m, eps, seed, t, lanes.astype(np.int32))
            for t in clocks]
    prog = make_program("2u-dp", epsilon=eps)

    spec = _spec("fused", quantiles=(0.5, 0.9, 0.99))
    fleet = QuantileFleet.create(spec, seed=11)
    for c in _chunks(seed=2, n=2):
        fleet = fleet.ingest(c)
    snap = Snapshot.capture(fleet)
    plane = _device_dp_release(snap.m_planes[0], eps, snap.seed,
                               snap.t_next, snap.lanes).reshape(G, 3)

    with jax.transfer_guard("disallow"):
        got = [prog.run_query((m,), t_next=t, seed=seed, lanes=lanes)
               for t in clocks]
        got_plane = snap.estimate_dp(eps)
        got_cols = [snap.estimate_dp(eps, quantile=q)
                    for q in spec.quantiles]
    for out in (*got, got_plane, *got_cols):
        assert isinstance(out, np.ndarray) and out.dtype == np.float32
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.view(np.int32), w.view(np.int32))
    np.testing.assert_array_equal(got_plane.view(np.int32),
                                  plane.view(np.int32))
    for qi, col in enumerate(got_cols):
        np.testing.assert_array_equal(col.view(np.int32),
                                      plane[:, qi].view(np.int32))


def _per_lane_clock_fleet(spec):
    fleet = QuantileFleet.create(spec, seed=13, per_lane_clock=True)
    rng = np.random.default_rng(1)
    n = spec.num_lanes
    for _ in range(30):
        lanes = rng.choice(n, size=n // 3, replace=False).astype(np.int32)
        fleet = fleet.tick_lanes_sparse(
            lanes, rng.normal(size=lanes.size).astype(np.float32))
    return fleet


@pytest.mark.parametrize("case", ["2u", "2u-window", "2u-dp",
                                  "2u-window-per-lane-clock"])
def test_one_quantile_read_is_its_column_of_the_plane(case):
    """A read with `quantile=` runs the release over that target's lanes
    alone (lane g·Q + qi); it must equal the full plane's column bit for
    bit, for the DP-gated read, the trusted read, and a `2u-dp` fleet's
    own release, on a scalar or a per-lane clock."""
    family = case.replace("-per-lane-clock", "")
    prog = make_program(family, window=24) if family == "2u-window" \
        else make_program(family, epsilon=0.7) if family == "2u-dp" \
        else make_program(family)
    spec = _spec("fused", program=prog, quantiles=(0.5, 0.9, 0.99))
    if case.endswith("per-lane-clock"):
        fleet = _per_lane_clock_fleet(spec)
        assert np.unique(fleet.query_view()[1]).size > 1
    else:
        fleet = QuantileFleet.create(spec, seed=3)
        for c in _chunks(seed=4, n=3):
            fleet = fleet.ingest(c)
    snap = Snapshot.capture(fleet)
    reads = [(snap.estimate_dp(0.5), lambda q: snap.estimate_dp(0.5, q)),
             (snap.estimate(), snap.estimate),
             (fleet.estimate(), fleet.estimate)]
    for plane, read in reads:
        assert plane.shape == (G, 3)
        for qi, q in enumerate(spec.quantiles):
            col = read(q)
            assert col.shape == (G,)
            np.testing.assert_array_equal(col.view(np.int32),
                                          plane[:, qi].view(np.int32))
    if family == "2u-dp":           # its own release, not a second draw
        np.testing.assert_array_equal(snap.estimate_dp(0.5), fleet.estimate())


# ------------------------------------------------------------- put-ahead
def test_prefetch_values_bit_identical_and_on_device():
    corpus = SyntheticCorpus(DataConfig(seed=3))
    plain = [corpus.batch(s) for s in range(4)]
    it = corpus.iterate(prefetch=1)
    for step in range(4):
        got = next(it)
        assert isinstance(got["tokens"], jax.Array)
        np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                      plain[step]["tokens"])
        np.testing.assert_array_equal(np.asarray(got["targets"]),
                                      plain[step]["targets"])
    # legacy synchronous path stays available and identical
    it0 = corpus.iterate(prefetch=0)
    np.testing.assert_array_equal(np.asarray(next(it0)["tokens"]),
                                  plain[0]["tokens"])


def test_prefetch_overlaps_source_with_consumer_compute():
    """Deterministic overlap proof (no wall-clock): with depth=1 the
    worker must have STARTED drawing item k+1 before the consumer asks for
    it. The source records draw starts; the consumer records pulls; for
    every pull k >= 1 the draw of k+1 must already have begun."""
    draws = []

    def source():
        for k in range(5):
            draws.append(k)
            yield np.full((2, 2), k, np.float32)

    it = prefetch_to_device(source(), depth=1)
    first = next(it)                # consumer takes item 0
    # worker is free to stage item 1 (and draw 2 into the queue slot);
    # wait (bounded) until the put-ahead actually drew item 1
    deadline = time.monotonic() + 5.0
    while len(draws) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert len(draws) >= 2, "no put-ahead: item 1 was never drawn while " \
                            "the consumer held item 0"
    np.testing.assert_array_equal(np.asarray(first), 0.0)
    rest = [int(np.asarray(x)[0, 0]) for x in it]
    assert rest == [1, 2, 3, 4]


def test_prefetch_relays_source_errors_with_type():
    def source():
        yield np.zeros((1, 2), np.float32)
        raise chaos.StreamFault("boom")

    it = prefetch_to_device(source(), depth=1)
    next(it)
    with pytest.raises(chaos.StreamFault, match="boom"):
        next(it)


def test_pipeline_counts_and_histograms():
    tel = Telemetry()
    pipe = IngestPipeline(depth=1, telemetry=tel)
    fleet = QuantileFleet.create(_spec("fused"), seed=0)
    versions = []
    pipe.run(fleet, _chunks(n=4), on_chunk=lambda f, n: versions.append(f))
    assert len(versions) == 4
    c = tel.counters()
    assert c["items_ingested"] == 4 * CHUNK_T
    assert c["chunks_ingested"] == 4
    lat = tel.latency_quantiles()
    assert lat["ingest_chunk_ms"]["p50"] >= 0.0
    assert np.isfinite(lat["ingest_chunk_ms"]["p99"])


# -------------------------------------------------------------- telemetry
def test_telemetry_counters_are_monotonic_and_thread_safe():
    tel = Telemetry()
    threads = [threading.Thread(
        target=lambda: [tel.count("x") for _ in range(500)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tel.counters()["x"] == 2000
    with pytest.raises(ValueError):
        tel.count("x", -1)
    with pytest.raises(KeyError):
        tel.observe_ms("nope", 1.0)


def test_telemetry_histogram_is_replayable():
    """Same observations through the same flush pattern -> identical
    frugal histogram state (the machinery is deterministic even though
    real latencies aren't)."""
    def feed():
        tel = Telemetry(seed=7)
        for i in range(50):
            tel.observe_ms("query_ms", float(i % 11))
            if i % 8 == 0:
                tel.flush()
        return tel.latency_quantiles()

    assert feed() == feed()


# ------------------------------------------------------------------- spans
INGEST_SPANS = ("ingest.stage", "ingest.wait_staged", "ingest.apply",
                "ingest.block")


class _capture:
    """A CPU `jax.profiler` capture into `tmp`; `.host` is the set of event
    names on the capture's `/host:` planes after it."""

    def __init__(self, tmp):
        self.dir = str(tmp)

    def __enter__(self):
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.host = {ev.name
                     for plane in jax.profiler.ProfileData.from_file(
                         path).planes if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events}


def _names(log):
    return [r.name for r in log.spans]


def test_outside_a_capture_no_span_is_recorded_and_histograms_fill(
        tmp_path):
    with _capture(tmp_path / "before"):
        with span("marker"):
            pass
    assert _names(recorded_spans()) == ["marker"]
    tel = Telemetry()
    svc = StreamingService(_spec("fused"), seed=0, telemetry=tel,
                           tenants=[TenantPolicy("ext", epsilon=0.5)])
    svc.ingest_stream(_chunks(n=3))
    svc.query()
    svc.query(tenant="ext")
    with span("outside") as s:
        pass
    assert s.ms >= 0.0
    # The log still holds the last capture's spans and nothing since.
    assert _names(recorded_spans()) == ["marker"]
    lat = tel.latency_quantiles()
    assert np.isfinite(lat["ingest_chunk_ms"]["p50"])
    assert np.isfinite(lat["query_ms"]["p50"])
    assert tel.counters()["chunks_ingested"] == 3
    assert set(tel.snapshot()) == {"counters", "latency_ms"}


def test_pipeline_spans_in_a_capture(tmp_path):
    pipe = IngestPipeline(depth=1, telemetry=Telemetry())
    with _capture(tmp_path) as cap:
        pipe.run(QuantileFleet.create(_spec("fused"), seed=0), _chunks(n=4))
    log = recorded_spans()
    assert log.dropped == 0
    by = {n: [r for r in log.spans if r.name == n] for n in INGEST_SPANS}
    for name, recs in by.items():
        assert sorted(r.key for r in recs) == [0, 1, 2, 3], name
        assert name in cap.host
    apply = {r.span_id: r for r in by["ingest.apply"]}
    for blk in by["ingest.block"]:
        parent = apply[blk.parent_id]
        assert parent.key == blk.key
        assert parent.start_ns <= blk.start_ns <= blk.end_ns <= parent.end_ns
    assert {r.thread for r in by["ingest.stage"]} == {"prefetch_to_device"}
    assert all(r.parent_id is None for r in by["ingest.wait_staged"])
    # A second run of the same pipeline numbers its chunks on from 4.
    with _capture(tmp_path / "again"):
        pipe.run(QuantileFleet.create(_spec("fused"), seed=0), _chunks(n=1))
    assert {r.key for r in recorded_spans().spans} == {4}


def test_read_spans_share_one_read_number(tmp_path):
    svc = StreamingService(_spec("fused"), seed=0,
                           tenants=[TenantPolicy("ext", epsilon=0.5)])
    svc.ingest(_chunks(n=1)[0])
    with _capture(tmp_path) as cap:
        svc.query()
        svc.query(tenant="ext", quantile=0.5)
    log = recorded_spans()
    reads = {r.key: r for r in log.spans if r.name == "query"}
    assert len(reads) == 2
    trusted, dp = sorted(reads)
    kids = {k: sorted(r.name for r in log.spans if r.parent_id ==
                      reads[k].span_id) for k in reads}
    assert kids == {trusted: ["query.snapshot"],
                    dp: ["query.dp_release", "query.snapshot"]}
    assert all(r.key in reads for r in log.spans)
    assert {"query", "query.snapshot", "query.dp_release"} <= cap.host


def test_a_second_capture_holds_only_its_own_spans(tmp_path):
    with _capture(tmp_path / "one"):
        with span("first", key=1):
            with span("first.child"):
                pass
    log = recorded_spans()
    assert _names(log) == ["first.child", "first"]
    assert log.spans[0].key == 1                 # taken from the parent
    # Back to back: no span runs between the two captures.
    with _capture(tmp_path / "two"):
        with span("second"):
            pass
    assert _names(recorded_spans()) == ["second"]


def test_spans_from_many_threads_keep_their_own_parents(tmp_path):
    """Threads recording at once lose no record and never take another
    thread's open span as a parent."""
    import sys

    n_threads, per = 2 * (os.cpu_count() or 1) + 2, 50

    def work(i):
        for j in range(per):
            with span("outer", key=i * per + j):
                with span("inner"):
                    pass

    threads = [threading.Thread(target=work, args=(i,), name=f"w{i}")
               for i in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with _capture(tmp_path):
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    log = recorded_spans()
    outer = {r.span_id: r for r in log.spans if r.name == "outer"}
    inner = [r for r in log.spans if r.name == "inner"]
    assert len(outer) == len(inner) == n_threads * per and log.dropped == 0
    assert len({r.span_id for r in log.spans}) == 2 * n_threads * per
    for r in inner:
        parent = outer[r.parent_id]
        assert (parent.thread, parent.key) == (r.thread, r.key)


def test_span_log_is_bounded_and_counts_what_it_dropped(tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(telemetry, "SPAN_LOG_LIMIT", 3)
    with _capture(tmp_path):
        for i in range(5):
            with span("tick", key=i):
                pass
    log = recorded_spans()
    assert [r.key for r in log.spans] == [0, 1, 2] and log.dropped == 2


def test_slo_fleet_threads_telemetry_and_snapshot_reads():
    from repro.serve.slo import SLOFleet

    tel = Telemetry()
    slo = SLOFleet(seed=0, telemetry=tel)
    for i in range(10):
        slo.observe(f"route-{i % 3}", "tok_q50_ms", float(i))
    slo.flush()
    c = tel.counters()
    assert c["slo_events_flushed"] == 10 and c["slo_flushes"] == 1
    snap = slo.snapshot()                      # service-snapshot read path
    plane = snap.estimate()
    for r, idx in slo._routes.items():
        assert plane[idx, 1] == pytest.approx(slo.estimate(r, "tok_q50_ms"))


def test_runtime_metadata_is_self_describing():
    meta = runtime_metadata()
    for key in ("unix_time", "wall_clock_utc", "device_count", "backend",
                "jax_version", "python_version", "cpu_count"):
        assert key in meta
    assert meta["device_count"] >= 1


# ------------------------------------------------------------------ misc api
def test_service_rejects_ambiguous_construction_and_double_start():
    with pytest.raises(ValueError, match="exactly one"):
        StreamingService()
    spec = _spec("fused")
    with pytest.raises(ValueError, match="exactly one"):
        StreamingService(spec, fleet=QuantileFleet.create(spec, seed=0))
    svc = StreamingService(spec, seed=0)
    svc.start(iter([]))
    # the empty stream may finish instantly, but start() guards on the
    # un-joined thread REFERENCE, not is_alive() — no race
    with pytest.raises(RuntimeError, match="join"):
        svc.start(iter([]))
    svc.join()


def test_join_reraises_ingest_errors():
    svc = StreamingService(_spec("fused"), seed=0)

    def dying():
        yield _chunks(n=1)[0]
        raise RuntimeError("source died")

    svc.start(dying())
    with pytest.raises(RuntimeError, match="source died"):
        svc.join()
    # the fully-applied chunk IS published
    assert svc.snapshot().items_ingested == CHUNK_T
