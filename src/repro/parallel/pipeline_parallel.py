"""REMOVED: seed-era GPipe pipeline schedule (never reachable from the
topology path).

The fleet's production placement is the (data × lane) 2-D mesh behind
parallel.topology.TopologySpec / parallel.mesh2d.Mesh2DFleet: lanes are
embarrassingly parallel and replicas merge through a pinned deterministic
fold, so a microbatch pipeline schedule has no role in the frugal serving
tier — `pipeline_forward` / `bubble_fraction` were only ever exercised by
their own subprocess test. They remain importable as ValueError stubs
naming the replacement (same convention as serve.engine.RouteStats; pinned
in tests/test_deprecations.py).
"""
from __future__ import annotations

_REMOVED = (
    "parallel.pipeline_parallel.{name} was removed: the GPipe microbatch "
    "schedule was a seed-era experiment never reachable from the fleet's "
    "topology path. Production placement is the (data x lane) 2-D mesh — "
    "declare FleetSpec(topology=TopologySpec(data=..., lanes=...)) "
    "(repro.api) or use parallel.mesh2d.Mesh2DFleet directly; "
    "DESIGN.md §15 documents the topology contract.")


def pipeline_forward(*args, **kwargs):
    raise ValueError(_REMOVED.format(name="pipeline_forward"))


def bubble_fraction(*args, **kwargs):
    raise ValueError(_REMOVED.format(name="bubble_fraction"))


__all__ = ["pipeline_forward", "bubble_fraction"]
