"""Dense GROUPBY fleet behind `StreamingService`: [T, G] chunks in, reads out.

Traffic parameters (chipbench/traffic/<mix>.json):

    pool_chunks     seeded [T, G] chunks made in set-up and cycled; each
                    one is copied from host memory afresh when staged
    arrival         the name of the file `arrivals/<arrival>.py` whose
                    `due(traffic, seconds)` gives each chunk's due offset
    readers         closed-loop reader threads, no think time
    read_mix        [[tenant, quantile], ...] cycled over all reads

The window drives `StreamingService.start`/`join`. It lasts `seconds`;
where chunks are still due but unpublished then, it closes at the next
publication, so a rate counts whole chunks over the time they took. The
check compares the state of sampled lanes, and every read served at those
lanes, with the plain reference from the same initial state and items.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time

import numpy as np

from yardstick import harness
from yardstick import reference as ref
from yardstick.trace import span
from yardstick.checks import sample_blocks, words_differing
from yardstick.traffic import (Releaser, flow_size_chunks, program_seed,
                               rng_for)

SAMPLE_BLOCKS = 16
SAMPLE_WIDTH = 256
JOIN_TIMEOUT_S = 120.0


class System:
    def __init__(self, config, traffic, *, seed, seconds, run):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.run = int(seed), float(seconds), run
        self.groups = int(config["num_groups"])
        self.quantiles = tuple(config["quantiles"])
        self.chunk_t = int(config["chunk_t"])
        self.readers = int(traffic.get("readers", 0))
        self.read_mix = [tuple(x) for x in traffic.get("read_mix", [])]
        self.epsilon = {t["name"]: float(t["epsilon"])
                        for t in config.get("tenants", [])}
        self.pseed = program_seed(seed)
        self.due = harness.load_module("arrivals", traffic["arrival"]).due(
            traffic, self.seconds)

    # ------------------------------------------------------------------ setup
    def setup(self):
        from repro.api import FleetSpec
        from repro.service import StreamingService, TenantPolicy

        class TimedService(StreamingService):
            """Records when each fleet version becomes visible."""

            def __init__(self, *a, **kw):
                self.published = []
                super().__init__(*a, **kw)

            def _publish(self, fleet, n_items):
                super()._publish(fleet, n_items)
                self.published.append(time.perf_counter())

        dist = self.config["items"]
        rng = rng_for(self.seed, 0)
        self.pool = list(flow_size_chunks(
            self.groups, int(self.traffic["pool_chunks"]), self.chunk_t, rng,
            mu_range=dist["mu"], sigma_range=dist["sigma"]))
        spec = FleetSpec(num_groups=self.groups, quantiles=self.quantiles,
                         chunk_t=self.chunk_t, program=self.config["program"])
        tenants = [TenantPolicy(n, epsilon=e) for n, e in self.epsilon.items()]
        self.svc = TimedService(spec, seed=self.pseed, tenants=tenants)
        # Warm-up: the first pool chunk through the service's own ingest,
        # then one read of each kind the window will make.
        self.sequence = [0]
        self.svc.ingest(self.pool[0])
        for tenant, q in dict.fromkeys(self.read_mix):
            self.svc.query(tenant, quantile=float(q))
        self.sample_lanes = np.concatenate([
            np.arange(s, s + SAMPLE_WIDTH) for s in sample_blocks(
                rng_for(self.seed, 1), self.groups * len(self.quantiles),
                SAMPLE_BLOCKS, SAMPLE_WIDTH)])
        self.sample_groups = np.unique(self.sample_lanes // len(self.quantiles))

    # ----------------------------------------------------------------- window
    def window(self, capture):
        svc, pool = self.svc, self.pool
        stop = threading.Event()
        done = threading.Event()
        reads, errors = [], []
        order = itertools.count()
        base = len(svc.published)

        def source():
            k = 0
            while not stop.is_set() and releaser.wait_for(k):
                with span("generate"):
                    idx = (len(self.sequence)) % len(pool)
                    self.sequence.append(idx)
                yield pool[idx]
                k += 1

        def reader():
            try:
                while not done.is_set():
                    tenant, q = self.read_mix[next(order) % len(self.read_mix)]
                    c0 = len(svc.published)
                    t_call = time.perf_counter()
                    with span("read"):
                        ans = svc.query(tenant, quantile=float(q))
                    t_done = time.perf_counter()
                    reads.append((t_call, t_done, tenant, float(q), c0,
                                  len(svc.published),
                                  np.array(ans[self.sample_groups])))
            except Exception as e:  # noqa: BLE001 — re-raised after join
                errors.append(e)

        if capture is not None:
            capture.start()
        t0 = time.perf_counter()
        releaser = Releaser(self.due, t0).start()
        svc.start(source())
        threads = [threading.Thread(target=reader, name=f"reader-{i}",
                                    daemon=True) for i in range(self.readers)]
        for th in threads:
            th.start()
        t_end = t0 + self.seconds
        while svc.ingest_running and time.perf_counter() < t_end:
            time.sleep(min(0.005, max(t_end - time.perf_counter(), 0.0005)))
        close = t_end
        n_end = len(svc.published)
        if releaser.released > n_end - base:
            # Chunks due and unpublished at the end: close at the next
            # publication, so the window holds whole chunks.
            limit = t_end + JOIN_TIMEOUT_S
            while (svc.ingest_running and len(svc.published) == n_end
                   and time.perf_counter() < limit):
                time.sleep(0.0005)
            if len(svc.published) > n_end:
                close = svc.published[n_end]
        if capture is not None:
            td = capture.stop()
        stop.set()
        releaser.stop()
        done.set()
        for th in threads:
            th.join(JOIN_TIMEOUT_S)
            if th.is_alive():
                raise RuntimeError("reader thread did not stop")
        svc.join(JOIN_TIMEOUT_S)
        releaser.join()
        if errors:
            raise errors[0]

        pub = np.asarray(svc.published[base:])
        in_window = int(np.count_nonzero(pub <= close))
        per_chunk = self.chunk_t * self.groups
        run = self.run
        run.window_s = close - t0
        run.events_visible = in_window * per_chunk
        run.attempted = in_window * per_chunk
        if reads:
            timed = [r for r in reads if r[0] < close]
            run.read_ms = np.asarray([(r[1] - r[0]) * 1e3 for r in timed])
            for kind, dp in (("read_trusted", False), ("read_dp", True)):
                run.host_spans_ms[kind] = np.asarray(
                    [(r[1] - r[0]) * 1e3 for r in timed
                     if (r[2] in self.epsilon) == dp])
        if capture is not None:
            run.trace = td
        self.reads = reads
        self.in_window = in_window
        self.lag = releaser.lag_summary()

    def info(self) -> dict:
        out = {"chunks_applied": len(self.sequence),
               "chunks_in_window": self.in_window,
               "reads": len(self.reads)}
        out.update(self.lag)
        return out

    # ------------------------------------------------------------------ check
    def release(self):
        """Copy what the check needs to the host, then free the program."""
        fleet = self.svc.fleet
        state = getattr(fleet.state, "sketch", fleet.state)
        self.got = tuple(np.asarray(p)[self.sample_lanes]
                         for p in state.planes())
        self.cursor = int(np.asarray(fleet.cursor.t_offset))
        del fleet, state
        self.svc = None
        gc.collect()

    def _reference(self, dtype):
        """Sampled lanes' state after every applied chunk."""
        lanes = ref.DenseLanes(self.sample_lanes, self.quantiles, self.pseed,
                               dtype=dtype)
        cols = [c[:, lanes.groups] for c in self.pool]
        states = [lanes.state]
        for idx in self.sequence:
            lanes.ingest(cols[idx])
            states.append(lanes.state)
        return states

    def check(self, checks, control=None):
        want = self._reference(np.float32)
        got = self.got
        if control == "bf16":
            low = self._reference(ref.BFLOAT16)
            got = tuple(p.astype(np.float32) for p in low[-1])
        checks.add("cursor_ticks_missing",
                   abs(len(self.sequence) * self.chunk_t - self.cursor), 0)
        checks.add("state_words_differing",
                   sum(words_differing(g, w) for g, w in zip(got, want[-1])),
                   0)
        if self.read_mix:
            low_m = [s[0].astype(np.float32) for s in low] \
                if control == "bf16" else None
            checks.add("reads_differing", self._reads_differing(want, low_m),
                       0)

    def _reads_differing(self, want, low_m) -> int:
        """Reads whose sampled answers match the reference at none of the
        cursors that could have been published while the read ran."""
        qs = len(self.quantiles)
        pos = {g: i for i, g in enumerate(self.sample_lanes)}
        bad = 0
        for (_, _, tenant, q, c0, c1, ans) in self.reads:
            lanes = self.sample_groups * qs + self.quantiles.index(q)
            idx = np.asarray([pos[int(x)] for x in lanes])
            if low_m is not None:
                ans = self._answer(low_m[min(c1, len(low_m) - 1)][idx],
                                   tenant, c1, lanes)
            ok = False
            for c in range(max(c0 - 1, 1), min(c1 + 1, len(want) - 1) + 1):
                exp = self._answer(want[c][0][idx], tenant, c, lanes)
                if words_differing(ans, exp) == 0:
                    ok = True
                    break
            bad += not ok
        return bad

    def _answer(self, m, tenant, chunks, lanes):
        if tenant in self.epsilon:
            return ref.dp_release(m, self.epsilon[tenant], self.pseed,
                                  chunks * self.chunk_t, lanes)
        return np.asarray(m, np.float32)
