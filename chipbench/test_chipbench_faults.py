"""The timed path broken underneath, and the rest of a run driven as it is:
each fault a cell can have must turn `correct` false. (No cell exchanges
data between chips, so that fault has no cell here.)"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.fleet import QuantileFleet
from repro.service.snapshot import Snapshot

_ingest = QuantileFleet.ingest
_estimate = Snapshot.estimate


def unchanged_chunk(self, items):
    t = int(np.shape(items)[0])
    return dataclasses.replace(self, cursor=self.cursor.advance(t))


def half_chunk(self, items):
    items = jnp.asarray(items)
    return _ingest(self, items.at[items.shape[0] // 2:].set(jnp.nan))


def altered_chunk(self, items):
    return _ingest(self, jnp.asarray(items).at[0].add(1.0))


def altered_answer(self, quantile=None):
    return _estimate(self, quantile) + np.float32(1.0)


FAULTS = [
    ("groupby-backfill", QuantileFleet, "ingest", unchanged_chunk),
    ("groupby-backfill", QuantileFleet, "ingest", half_chunk),
    ("groupby-backfill", QuantileFleet, "ingest", altered_chunk),
    ("groupby-reads", QuantileFleet, "ingest", unchanged_chunk),
    ("groupby-reads", QuantileFleet, "ingest", half_chunk),
    ("groupby-reads", QuantileFleet, "ingest", altered_chunk),
    ("groupby-reads", Snapshot, "estimate", altered_answer),
]


@pytest.mark.parametrize("workload,owner,attr,fault", FAULTS,
                         ids=[f"{w}-{f.__name__}" for w, _, _, f in FAULTS])
def test_fault_is_not_correct(run_tiny, monkeypatch, workload, owner, attr,
                              fault):
    monkeypatch.setattr(owner, attr, fault)
    res = run_tiny(workload)
    assert res["correct"] is False, res["checks"]
