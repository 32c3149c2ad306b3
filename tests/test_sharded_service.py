"""The lane-sharded fleet on the served path: `StreamingService` over
`FleetSpec(topology=TopologySpec(lanes=4))`, on four host CPU devices in a
child process (so that it runs when this process started JAX with one
device).

Fed [T, G] chunks through `ingest` and `start`/`join`, the sharded service
agrees bit for bit with the single-device service and with the paper's
scalar Algorithm 3 (`core/reference.py`) on every lane; its staged chunks
carry the fleet's item sharding, one [T, Gp/4] slab per device (Gp: the
groups padded to whole shards); each chunk leaves four
`ingest.stage.shard` spans under its `ingest.stage`, and
`ingest.bytes_staged` counts its bytes.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.api import FleetSpec, QuantileFleet

_CHILD = r"""
import json, tempfile
import numpy as np, jax
from repro.api import FleetSpec, QuantileFleet
from repro.core import rng as crng
from repro.core.reference import frugal2u_scalar
from repro.parallel.topology import TopologySpec
from repro.service import StreamingService
from repro.service.telemetry import recorded_spans

assert len(jax.devices()) == 4, jax.devices()
SEED = 3
CASES = {
    "q2": (64, (0.5, 0.99), (16, 16, 5, 64)),
    "q2-ragged": (10, (0.5, 0.99), (7, 32, 1, 40)),
    "q1-ragged": (13, (0.25,), (64, 3, 16)),
    "q3": (8, (0.1, 0.5, 0.9), (1, 100)),
}
staged = []
_ingest = QuantileFleet.ingest


def spy(self, items):
    if isinstance(items, jax.Array) and self.item_sharding is not None:
        staged.append({
            "sharding": items.sharding == self.item_sharding,
            "shards": sorted([list(s.data.shape), s.device.id]
                             for s in items.addressable_shards)})
    return _ingest(self, items)


QuantileFleet.ingest = spy
out = {}
for name, (g, qs, cuts) in CASES.items():
    t = sum(cuts)
    # Whole-number items keep every step whole, so the float64 oracle and
    # the float32 lanes agree exactly.
    items = np.random.default_rng(g).integers(0, 800, (t, g)) \
        .astype(np.float32)
    chunks = np.split(items, np.cumsum(cuts)[:-1])
    one = StreamingService(FleetSpec(num_groups=g, quantiles=qs, chunk_t=16),
                           seed=SEED)
    four = StreamingService(FleetSpec(num_groups=g, quantiles=qs, chunk_t=16,
                                      topology=TopologySpec(lanes=4)),
                            seed=SEED)
    staged.clear()
    for svc in (one, four):
        if svc is four:
            jax.profiler.start_trace(tempfile.mkdtemp())
        svc.ingest(chunks[0])
        svc.start(iter(chunks[1:]))
        svc.join()
    jax.profiler.stop_trace()
    spans = recorded_spans().spans
    stage = {r.key: r.span_id for r in spans if r.name == "ingest.stage"
             and r.thread == "prefetch_to_device"}
    est = four.fleet.estimate()
    want = np.asarray([[frugal2u_scalar(
        items[:, gi], crng.counter_uniform_host(
            SEED, np.arange(t), gi * len(qs) + qi), quantile=q)
        for qi, q in enumerate(qs)] for gi in range(g)], np.float32)
    a, b = one.fleet.state, four.fleet.state.unshard()
    out[name] = {
        "item_sharding": four.fleet.item_sharding is not None,
        "vs_single": int(sum(np.count_nonzero(
            np.asarray(getattr(a, f)).view(np.uint32)
            != np.asarray(getattr(b, f)).view(np.uint32))
            for f in ("m", "step", "sign"))),
        "estimate_vs_single": bool(np.array_equal(
            est.view(np.uint32), one.fleet.estimate().view(np.uint32))),
        "vs_oracle": int(np.count_nonzero(
            est.view(np.uint32) != want.view(np.uint32))),
        "staged": list(staged),
        "shard_spans": sorted(
            [r.key, r.parent_id == stage.get(r.key), r.thread]
            for r in spans if r.name == "ingest.stage.shard"),
        "chunks": len(cuts),
        "bytes_staged": four.stats()["counters"]["ingest.bytes_staged"],
        "t": t,
    }
print("RESULTS " + json.dumps(out))
"""

CASES = ("q2", "q2-ragged", "q1-ragged", "q3")


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                        + env.get("XLA_FLAGS", "")).strip()
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS ")][-1]
    return json.loads(line[len("RESULTS "):])


@pytest.mark.parametrize("case", CASES)
def test_sharded_service_is_bit_exact(served, case):
    """Every lane's words equal the single-device service's, and every
    estimate Algorithm 3's on the lane's own counter uniforms."""
    got = served[case]
    assert got["vs_single"] == 0
    assert got["estimate_vs_single"]
    assert got["vs_oracle"] == 0


def test_staged_chunks_carry_the_item_sharding(served):
    """Every chunk the pipeline hands `ingest` is placed by the fleet's
    item sharding, one [T, Gp/4] slab per device, where Gp pads the groups
    to whole shards: 64 groups stay 64, a ragged 10 (two quantiles) pads
    to 12 and 13 (one) to 16."""
    for case, (cuts, width) in {"q2": ((16, 16, 5, 64), 16),
                                "q2-ragged": ((7, 32, 1, 40), 3),
                                "q1-ragged": ((64, 3, 16), 4)}.items():
        got = served[case]
        assert got["item_sharding"]
        # The synchronous first chunk and those the ingest thread staged.
        assert all(s["sharding"] for s in got["staged"])
        assert [sorted(tuple(x[0]) for x in s["shards"])
                for s in got["staged"]] == [[(t, width)] * 4 for t in cuts]
        assert [sorted(x[1] for x in s["shards"]) for s in got["staged"]] \
            == [[0, 1, 2, 3]] * len(cuts)


@pytest.mark.parametrize("case", CASES)
def test_stage_shard_spans_four_per_chunk_under_ingest_stage(served, case):
    got = served[case]
    spans = got["shard_spans"]
    keys = [k for k, _, _ in spans]
    assert keys == [k for k in range(got["chunks"]) for _ in range(4)]
    assert all(under for _, under, _ in spans)
    assert all(th == "prefetch_to_device" for _, _, th in spans)


@pytest.mark.parametrize("case", CASES)
def test_bytes_staged_counts_every_chunk(served, case):
    """T x Gp/Q x 4 bytes a chunk: the padded columns are staged too."""
    got = served[case]
    cols = {"q2": 64, "q2-ragged": 12, "q1-ragged": 16, "q3": 8}[case]
    assert got["bytes_staged"] == got["t"] * cols * 4


def test_single_placement_stages_on_the_default_device():
    fleet = QuantileFleet.create(FleetSpec(num_groups=8,
                                           quantiles=(0.5, 0.99)))
    assert fleet.item_sharding is None
    items = np.ones((4, 8), np.float32)
    staged = fleet.stage(items)
    assert staged.sharding.device_set == {jax.devices()[0]}
    assert np.array_equal(np.asarray(staged), items)
