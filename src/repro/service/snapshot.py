"""Consistent copy-on-query reads: a `Snapshot` pins one fleet version.

The snapshot protocol is the service's whole consistency story:

  1. The server publishes a NEW immutable `QuantileFleet` object per
     applied chunk (functional ingest — the previous version is never
     mutated), swapping one reference under a lock.
  2. A reader pins the current reference (one lock-protected read), then
     gathers HOST COPIES of only the program's `layout.query_fields`
     planes plus the cursor — `QuantileFleet.query_view()`. Readers never
     block ingest beyond that reference swap, and ingest never blocks
     readers.
  3. Because the copies are real (`np.array(copy=True)`), a snapshot
     survives the producer moving on — including `tick_lanes_sparse
     (donate=True)` rounds that overwrite the old device buffers IN
     PLACE. A zero-copy "view" here would be the classic aliased-donation
     bug; the test suite pins that it is not one.

Every answer is bit-reproducible offline: `(m_planes, t_next, seed,
lanes)` fully determine `program.run_query`, including the `2u-dp`
program's Laplace noise (keyed on `(seed ^ salt, t_next, lane)`), so a
served answer can be audited against a single-threaded replay of the same
cursor — the e14 bench asserts exactly that for every query it serves.

`chaos.on_query_event()` fires mid-capture (fault kind `query_stall`):
a reader dying between pinning the fleet version and finishing the gather
must leave ingest untouched, and the retried capture must answer
bit-identically.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from repro.api.fleet import QuantileFleet, quantile_column
from repro.core.program import LaneProgram, make_program
from repro.resilience import chaos

from .telemetry import span


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """An immutable, host-owned view of one fleet version at one cursor.

    Holds only the query planes (1-2 words per lane — a windowed program's
    two m planes at most) plus the cursor scalars; never step/sign words,
    never device buffers.
    """

    program: LaneProgram
    num_groups: int
    num_quantiles: int
    quantiles: Tuple[float, ...]
    m_planes: Tuple[np.ndarray, ...]
    t_next: np.ndarray            # scalar () or per-lane [L] int32
    seed: int
    lanes: np.ndarray             # absolute lane ids [L]

    @classmethod
    def capture(cls, fleet: QuantileFleet,
                telemetry=None) -> "Snapshot":
        """Copy-on-query capture of `fleet` (the caller has already pinned
        which version). `telemetry` (optional, duck-typed `.count`) records
        stall counts; the server times the full query round-trip itself.
        The capture is the span `query.snapshot`."""
        try:
            with span("query.snapshot"):
                # The worst place for a reader to die: version pinned,
                # gather not yet done. chaos injects QueryStalled here.
                chaos.on_query_event()
                m_planes, t_next, seed, lanes = fleet.query_view()
        except chaos.QueryStalled:
            if telemetry is not None:
                telemetry.count("queries_stalled")
            raise
        return cls(program=fleet.spec.program,
                   num_groups=fleet.num_groups,
                   num_quantiles=fleet.num_quantiles,
                   quantiles=fleet.spec.quantiles,
                   m_planes=m_planes, t_next=t_next, seed=seed, lanes=lanes)

    # ------------------------------------------------------------------ reads
    @property
    def items_ingested(self) -> int:
        """Items behind this snapshot (scalar-clock fleets): the replay key
        an offline auditor feeds the same stream up to."""
        t = np.asarray(self.t_next)
        if t.ndim != 0:
            raise ValueError("per-lane clock snapshot has no single item "
                             "count; read t_next directly")
        return int(t)

    def _view(self, quantile: Optional[float]):
        """The cursor's query view; with `quantile=` only that target's
        lanes (`quantile_column`)."""
        view = (self.m_planes, self.t_next, self.seed, self.lanes)
        if quantile is None:
            return view
        return quantile_column(view, self.num_quantiles,
                               self.quantiles.index(float(quantile)))

    def _shaped(self, released, quantile: Optional[float]) -> np.ndarray:
        released = np.asarray(released)
        if quantile is not None:
            return released
        return released.reshape(self.num_groups, self.num_quantiles)

    def estimate(self, quantile: Optional[float] = None) -> np.ndarray:
        """[G, Q] estimates via the program's own query (the trusted read:
        for a `2u-dp` program this is already the noised release); with
        `quantile=` one tracked target's [G] column, queried over that
        target's lanes alone."""
        m_planes, t_next, seed, lanes = self._view(quantile)
        return self._shaped(self.program.run_query(
            m_planes, t_next=t_next, seed=seed, lanes=lanes), quantile)

    def estimate_dp(self, epsilon: float,
                    quantile: Optional[float] = None) -> np.ndarray:
        """DP-gated release for untrusted tenants: the program's answer
        passed through the `2u-dp` output-perturbation query at `epsilon`
        — Laplace noise keyed on `(seed ^ salt, t_next, lane)`, so the
        release is deterministic at a cursor (same snapshot, same tenant
        question, same noised answer — replayable for audit).

        A fleet already running `2u-dp` releases through its OWN calibrated
        noise; stacking a second draw would double-spend the budget.
        The second release is the span `query.dp_release`: host numpy over
        the answered lanes only (one [G] column with `quantile=`), with no
        JAX call and no transfer."""
        if self.program.family == "2u-dp":
            return self.estimate(quantile)
        m_planes, t_next, seed, lanes = self._view(quantile)
        base = self.program.run_query(m_planes, t_next=t_next, seed=seed,
                                      lanes=lanes)
        with span("query.dp_release"):
            dp = make_program("2u-dp", epsilon=float(epsilon))
            released = dp.run_query((base,), t_next=t_next, seed=seed,
                                    lanes=lanes)
        return self._shaped(released, quantile)
