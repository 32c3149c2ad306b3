"""QuantileFleet — the one fleet API over every frugal backend.

The paper's pitch is "estimate ANY quantile for each of a large number of
groups with one or two words of memory". Before this facade the repo's
public surface had fractured into five entry points (sketch.process,
kernels.ops auto entry points, core.streaming.ingest_stream/_array,
parallel.ShardedGroupFleet, serve.SLOFleet), each hand-threading
`(seed, t_offset, g_offset)` and each tracking a single quantile target.
QuantileFleet folds them into one surface:

    spec  = FleetSpec(num_groups=4096, quantiles=(0.5, 0.95, 0.99))
    fleet = QuantileFleet.create(spec, seed=0)
    fleet = fleet.ingest(items)          # [t, G] block; cursor auto-advances
    fleet.estimate()                     # [G, Q] numpy
    fleet.checkpoint(ckpt_dir, step=n)   # format-4, checksummed, bit-exact resume

Design points:

  * **Explicit cursor.** Fleet state carries a StreamCursor(seed, t_offset,
    g_offset) pytree; every ingest returns a new fleet whose cursor has
    advanced. Users never thread offsets; checkpoints restore the cursor so
    the resumed trajectory is bit-identical to the uninterrupted one.
  * **Multi-quantile lanes.** quantiles=(q0..qQ-1) lays out a (G × Q) lane
    plane, lane = g·Q + qi, flattened through the whole stack (scan, fused
    kernels, lane-axis sharding). Each lane hashes its own uniform stream
    off its ABSOLUTE lane id, so a Q=1 fleet is bit-identical to the legacy
    single-target sketch and Q>1 estimates are invariant to chunking and to
    how lanes land on devices.
  * **Placement-declarative.** `FleetSpec(topology=TopologySpec(data=R,
    lanes=S))` is the one placement surface: single-device fleets run the
    jnp/fused engines, a lane-sharded topology runs the 1-D sharded fleet,
    and data>1 runs the 2-D (data × lane) mesh (parallel.mesh2d) whose
    replicas ingest disjoint chunk shards and merge through the pinned
    deterministic rule of DESIGN.md §15. Trajectories are bit-identical
    across every placement (the counter RNG keys on absolute (seed, tick,
    lane) — DESIGN.md §4); `reshard(topology)` re-places a LIVE fleet.
  * **Event-stream lanes.** A per-lane cursor (t_offset as an [L] vector)
    supports sparse event ingestion — `tick_lanes` / `tick_lanes_sparse` —
    where each lane's k-th event consumes uniform (seed, k, lane)
    regardless of batching. serve.SLOFleet runs on exactly this.
  * **Resilient by construction.** `ingest_stream` is crash-consistent: a
    dying source surfaces as a resumable chaos.StreamInterrupted carrying
    the fleet advanced through every fully-applied chunk, and
    `skip_items=err.items_applied` replays only the uncommitted suffix —
    bit-exact with the uninterrupted run. `health()`/`check_health()` scan
    the lane planes against the program's declared StateLayout invariants
    and apply FleetSpec's health policy ("raise" / "quarantine" /
    "ignore"); quarantined lanes are re-initialized in place and, because
    uniforms key on the absolute (seed, tick, lane), tick on bit-exactly
    like lanes created at the current cursor (DESIGN.md §12).

The facade is a registered pytree (spec static, state + cursor dynamic), so
jnp-backend fleets ride inside jitted train/serve steps — the monitor
fleets do.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Iterable, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import frugal, streaming
from repro.core import program as program_mod
from repro.core import rng as crng
from repro.core.sketch import GroupedQuantileSketch
from repro.kernels import ops as kernel_ops
from repro.parallel.group_sharding import ShardedGroupFleet
from repro.parallel.mesh2d import Mesh2DFleet
from repro.parallel.topology import TopologySpec
from repro.resilience import chaos
from repro.resilience import health as health_mod

from .spec import FleetSpec, StreamCursor

Array = jax.Array


# One program-generic event-lane tick pair replaces the old four
# algo/drift-specialized signatures: the plane-tuple WIDTH derives from the
# program's StateLayout (a 1U fleet moves one [L] buffer, a windowed 2U
# fleet six — no placeholder shadow buffers ever ride a dispatch), and the
# program's tick function is the body. `program` is the static compile key
# (a core.program.family_base instance — rule scalars travel dynamically).
@functools.partial(jax.jit, static_argnames=("program",))
def _lane_tick(planes, ticks, q, items, seed, g_offset, scalars, program):
    """One vectorized tick over L lanes: uniforms key on (seed, per-lane or
    scalar tick, absolute lane id); NaN items are bit-exact no-ops."""
    g_ids = jnp.asarray(g_offset, jnp.int32) \
        + jnp.arange(planes[0].shape[0], dtype=jnp.int32)
    r = crng.counter_uniform(seed, ticks, g_ids)
    ctx = frugal.TickCtx(quantile=q, t=ticks, seed=seed, lanes=g_ids,
                         scalars=scalars)
    return program.run_tick(planes, items, r, ctx)


def _check_sparse_lanes(lanes, items, mask):
    """Opt-in debug check for the tick_lanes_sparse lane contract: masked-in
    lanes must be DISTINCT (a lane's same-round events would race in the
    scatter and share one tick's uniform) and no masked-out pad slot may
    name a masked-in lane (duplicate scatter indices write in undefined
    order — the pad's unchanged state could clobber the real update).
    Host-side and eager-only by design: it is a debugging aid, not a hot
    path."""
    try:
        ln = np.asarray(lanes)
        if mask is None:
            mk = ~np.isnan(np.asarray(items))
        else:
            mk = np.asarray(mask) != 0
    except jax.errors.TracerArrayConversionError as e:
        raise ValueError(
            "check_duplicates needs concrete (eager) lanes/mask — drop the "
            "flag inside jit") from e
    real = ln[mk]
    uniq, counts = np.unique(real, return_counts=True)
    dupes = uniq[counts > 1]
    if dupes.size:
        raise ValueError(
            f"tick_lanes_sparse: lanes {dupes[:8].tolist()} repeat within "
            "one round — split same-lane events into successive calls in "
            "arrival order (serve.SLOFleet.flush does this)")
    bad_pads = np.intersect1d(ln[~mk], uniq)
    if bad_pads.size:
        raise ValueError(
            f"tick_lanes_sparse: masked-out pad slots reuse event lanes "
            f"{bad_pads[:8].tolist()} — pad with lanes that have NO event "
            "this round (duplicate scatter indices write in undefined "
            "order)")


def quantile_column(view, num_quantiles: int, qi: int):
    """A query view `(m_planes, t_next, seed, lanes)` cut to one tracked
    target: lane g·Q + qi holds group g's target qi, so the column is every
    Q-th lane from qi (and every Q-th tick of a per-lane clock). Every
    program's query is per lane, so its release over the column is the
    column of its release over all lanes, bit for bit."""
    m_planes, t_next, seed, lanes = view
    q = num_quantiles
    if np.ndim(t_next) == 1:
        t_next = t_next[qi::q]
    return tuple(p[qi::q] for p in m_planes), t_next, seed, lanes[qi::q]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantileFleet:
    """A (G × Q) fleet of frugal quantile lanes behind one ingest/query API.

    Functional: every mutating call returns a new fleet. `state` is the lane
    sketch (host/single-device for single placement, lane-sharded for a 1-D
    topology, replica-stacked Mesh2DFleet for a 2-D one); `cursor` is the
    fleet's absolute stream position.
    """

    state: Union[GroupedQuantileSketch, ShardedGroupFleet, Mesh2DFleet]
    cursor: StreamCursor
    spec: FleetSpec = dataclasses.field(metadata=dict(static=True))

    # -------------------------------------------------------------- creation
    @classmethod
    def create(cls, spec: FleetSpec, init: Union[float, Array] = 0.0,
               seed: int = 0, key: Optional[Array] = None,
               cursor: Optional[StreamCursor] = None,
               per_lane_clock: bool = False) -> "QuantileFleet":
        """Fresh fleet at stream position 0.

        `seed` (or a JAX PRNG `key`) seeds the counter RNG. `per_lane_clock`
        starts the cursor with a per-lane [L] tick vector — the event-stream
        mode (`tick_lanes`); block ingest (`ingest`/`ingest_stream`) uses
        the default scalar clock.
        """
        sk = GroupedQuantileSketch.create_lanes(
            spec.num_groups, spec.quantiles, algo=spec.algo, init=init,
            drift=spec.drift)
        if cursor is None:
            t0 = jnp.zeros((spec.num_lanes,), jnp.int32) if per_lane_clock \
                else 0
            cursor = StreamCursor.create(seed=seed, t_offset=t0, key=key)
        state = cls._place(spec, sk)
        return cls(state=state, cursor=cursor, spec=spec)

    @staticmethod
    def _place(spec: FleetSpec, sk: GroupedQuantileSketch):
        """Lay a canonical [L] sketch out on the spec's topology. For the
        2-D mesh every replica starts at the canonical state — placement
        from a sketch is by definition a sync point (DESIGN.md §15)."""
        if spec.backend == "sharded":
            return ShardedGroupFleet.from_sketch(
                sk, spec.mesh, lanes_per_group=spec.num_quantiles)
        if spec.backend == "mesh2d":
            return Mesh2DFleet.from_sketch(
                sk, spec.topology, lanes_per_group=spec.num_quantiles)
        return sk

    # ------------------------------------------------------------ properties
    @property
    def num_groups(self) -> int:
        return self.spec.num_groups

    @property
    def num_quantiles(self) -> int:
        return self.spec.num_quantiles

    @property
    def num_lanes(self) -> int:
        return self.spec.num_lanes

    @property
    def algo(self) -> str:
        return self.spec.algo

    def memory_words(self) -> int:
        """Persistent words per lane — 1 (1U) or 2 (packed 2U), the paper's
        claim; Q targets per group cost Q·memory_words() words."""
        return self.spec.memory_words()

    def _lane_sketch(self) -> GroupedQuantileSketch:
        """The canonical [L]-lane sketch view of `state` (host-gathering if
        sharded; for a 2-D fleet the replicas fold through the pinned merge
        rule — reading here is a merge, not a mutation)."""
        if isinstance(self.state, (ShardedGroupFleet, Mesh2DFleet)):
            return self.state.unshard()
        return self.state

    # ---------------------------------------------------------------- health
    def health(self) -> health_mod.HealthReport:
        """Scan-only lane health report: every lane's planes checked against
        the spec program's declared StateLayout invariants (finite heads,
        exact ±1 signs, pack-round-trippable steps — resilience.health).
        Never mutates or raises; `check_health` applies the policy."""
        sk = self._lane_sketch()
        return health_mod.report_for(self.spec.program, sk.planes(),
                                     self.spec.health)

    def check_health(self) -> Tuple["QuantileFleet", "health_mod.HealthReport"]:
        """Scan lane health and APPLY spec.health: returns (fleet, report).

        "raise"      — LaneCorruptionError if any lane is corrupt;
        "quarantine" — corrupt lanes re-initialized in place (fresh default
                       lane state; future ticks bit-exact with a lane
                       CREATED at the current cursor — counter-hashed
                       uniforms make healing ripple-free), healthy lanes
                       untouched bit-for-bit;
        "ignore"     — report only.

        On a 2-D placement the scan and the heal run over the MERGED
        canonical lanes, and re-placing the healed sketch broadcasts it to
        every replica — quarantine is a sync point (DESIGN.md §15).
        """
        rep = self.health()
        if rep.healthy or self.spec.health == "ignore":
            return self, rep
        if self.spec.health == "raise":
            raise health_mod.LaneCorruptionError(str(rep))
        sk = self._lane_sketch()
        prog = self.spec.program
        mask = health_mod.validate_planes(prog, sk.planes())
        healed = sk.with_planes(
            health_mod.heal_planes(prog, sk.planes(), mask))
        rep = dataclasses.replace(rep, quarantined=rep.corrupt_lanes)
        return dataclasses.replace(
            self, state=self._place(self.spec, healed)), rep

    # ---------------------------------------------------------- block ingest
    @property
    def item_sharding(self) -> Optional[jax.sharding.Sharding]:
        """How `stage` places a chunk: for a lane-sharded fleet each
        device's group columns on that device (`NamedSharding(mesh,
        P(None, axis))`, over the columns padded to whole shards); None,
        the default device, for the single placement and a 2-D mesh (it
        routes row slabs itself)."""
        if isinstance(self.state, ShardedGroupFleet):
            return self.state.item_sharding
        return None

    def stage(self, items, shard_span=contextlib.nullcontext) -> Array:
        """A [t, G] chunk put where `ingest` reads it, the put-ahead step of
        the served path. The single placement and a 2-D mesh take it whole
        on the default device (`jax.device_put`); a lane-sharded fleet pads
        its columns to whole shards and puts each device's columns on that
        device, so no device holds a whole chunk, with `shard_span()`
        around the wait for each device's columns."""
        if isinstance(self.state, ShardedGroupFleet):
            return self.state._pad_items(self._as_items(items), shard_span)
        return jax.device_put(items)

    def _as_items(self, items) -> Array:
        width = (self.num_groups,)
        sharded = isinstance(self.state, ShardedGroupFleet)
        if sharded:
            # A staged chunk carries the shards' pad columns.
            width += (self.state.padded_groups // self.num_quantiles,)
        if sharded and not isinstance(items, jax.Array):
            # Host columns stay on the host: the sharded fleet places each
            # shard's columns on its own device.
            items = np.asarray(items, np.float32)
        else:
            items = jnp.asarray(items, jnp.float32)
        if items.ndim == 1:
            items = items[:, None]
        if items.ndim != 2 or items.shape[1] not in width:
            raise ValueError(
                f"items shape {items.shape} != [t, {self.num_groups}]")
        return items

    def _require_scalar_clock(self, what: str):
        if self.cursor.per_lane:
            raise ValueError(
                f"{what} needs the scalar stream clock; this fleet uses a "
                "per-lane cursor (event-stream mode) — use tick_lanes")

    def ingest(self, items) -> "QuantileFleet":
        """Ingest a [t, G] block (one item per group per tick); returns the
        fleet advanced t ticks. Bit-identical for any split of a stream into
        successive ingest calls, and across backends."""
        self._require_scalar_clock("ingest")
        items = self._as_items(items)
        t = items.shape[0]
        cur = self.cursor
        q = self.num_quantiles
        if isinstance(self.state, (ShardedGroupFleet, Mesh2DFleet)):
            state = self.state.ingest_array(
                items, seed=cur.seed, chunk_t=self.spec.chunk_t,
                t_offset=int(cur.t_offset), g_offset=int(cur.g_offset))
        elif self.spec.backend == "jnp":
            state = self.state.process_seeded(
                items, cur.seed, t_offset=cur.t_offset,
                g_offset=cur.g_offset, lanes_per_group=q)
        else:
            state = streaming.ingest_array(
                self.state, items, seed=cur.seed, chunk_t=self.spec.chunk_t,
                t_offset=cur.t_offset, g_offset=cur.g_offset,
                lanes_per_group=q)
        return dataclasses.replace(self, state=state, cursor=cur.advance(t))

    def ingest_stream(self, chunks: Iterable,
                      chunk_t: Optional[int] = None,
                      skip_items: int = 0) -> "QuantileFleet":
        """Ingest an unbounded host-side stream of [t_i, G] blocks with
        O(chunk_t · G) transient memory (core.streaming re-chunker under the
        hood — identical blocking, bit-identical result to `ingest` of the
        concatenated stream). The cursor advances by the number of REAL
        items, so successive calls continue the uniform stream seamlessly.

        Crash consistency: if the source raises mid-stream, the exception
        re-raises as a resumable chaos.StreamInterrupted whose `fleet` is
        THIS fleet advanced through every fully-applied chunk (cursor
        included) and whose `items_applied` counts the committed leading
        items of the ORIGINAL stream (skip_items-cumulative). Resume with

            fleet = err.fleet.ingest_stream(same_stream,
                                            skip_items=err.items_applied)

        and the final state is bit-identical to the uninterrupted run —
        no item is ever dropped or double-applied (tests/test_resilience.py
        kills ingest at every chunk boundary to prove it). `skip_items`
        drops that many leading real rows host-side before any work."""
        self._require_scalar_clock("ingest_stream")
        chunk_t = chunk_t or self.spec.chunk_t
        cur = self.cursor
        skip_items = int(skip_items)
        if skip_items:
            chunks = streaming.drop_leading_items(chunks, skip_items,
                                                  self.num_groups)
        counted = [0]

        def counting():
            for c in chunks:
                # np.shape reads .shape off arrays (incl. device-resident
                # jax arrays — no D2H copy); only shapeless host sequences
                # get converted.
                shape = np.shape(c)
                counted[0] += shape[0] if shape else 1
                yield c

        try:
            if isinstance(self.state, (ShardedGroupFleet, Mesh2DFleet)):
                state = self.state.ingest_stream(
                    counting(), seed=cur.seed, chunk_t=chunk_t,
                    t_offset=int(cur.t_offset), g_offset=int(cur.g_offset))
            elif self.spec.backend == "jnp":
                state = self._ingest_stream_jnp(counting(), chunk_t, counted)
            else:
                state = streaming.ingest_stream(
                    self.state, counting(), seed=cur.seed, chunk_t=chunk_t,
                    t_offset=int(cur.t_offset), g_offset=cur.g_offset,
                    lanes_per_group=self.num_quantiles)
        except chaos.StreamInterrupted as e:
            applied = e.items_applied
            partial = dataclasses.replace(self, state=e.state,
                                          cursor=cur.advance(applied))
            total = skip_items + applied
            raise chaos.StreamInterrupted(
                f"{e}; resume with err.fleet.ingest_stream(stream, "
                f"skip_items={total}) over the ORIGINAL stream",
                state=e.state, fleet=partial, items_applied=total) from e
        return dataclasses.replace(self, state=state,
                                   cursor=cur.advance(counted[0]))

    def _ingest_stream_jnp(self, chunks, chunk_t: int, counted):
        """jnp-backend stream loop — mirrors core.streaming.ingest_stream's
        crash-consistency contract (fully-applied chunks only; staged
        partial buffers die with the interrupt) over process_seeded."""
        cur = self.cursor
        state = self.state
        t_base = int(cur.t_offset)
        applied = 0
        blocks = streaming.rechunk_blocks(chunks, self.num_groups, chunk_t)
        while True:
            try:
                block, t0 = next(blocks)
            except StopIteration:
                break
            except (ValueError, TypeError):
                raise   # malformed input — not resumable
            except Exception as e:
                raise chaos.StreamInterrupted(
                    f"stream source failed after {applied} applied "
                    f"item(s): {e}", state=state,
                    items_applied=applied) from e
            state = state.process_seeded(
                jnp.asarray(block), cur.seed,
                t_offset=crng.wrap_i32(t_base + t0),
                g_offset=cur.g_offset,
                lanes_per_group=self.num_quantiles)
            applied = min(counted[0], applied + chunk_t)
            state = chaos.corrupt_sketch(state, t_base + int(t0),
                                         t_base + int(t0) + chunk_t)
            try:
                chaos.count_event("ingest")
            except chaos.StreamFault as e:
                raise chaos.StreamInterrupted(
                    f"stream fault after {applied} applied item(s): {e}",
                    state=state, items_applied=applied) from e
        return state

    # ---------------------------------------------------------- event ingest
    def tick_lanes(self, items, mask=None) -> "QuantileFleet":
        """One vectorized tick over ALL L lanes from lane-level items [L]
        (NaN = no event on that lane: a bit-exact no-op).

        With a per-lane cursor, each lane's clock advances only where `mask`
        is 1 (default: where items are non-NaN) — a lane's k-th event always
        consumes uniform (seed, k, lane) regardless of batching. Items on
        masked-OUT lanes are forced to NaN first, so mask 0 is a TRUE no-op:
        a lane's state never moves without its clock (the counter-RNG stream
        would silently desync). With the scalar clock every lane shares the
        tick and the clock advances by 1 (block semantics — what the in-step
        monitor fleets use); a mask is meaningless there and raises. jit-
        safe: jnp-backend fleets may call this inside a traced step.
        """
        if isinstance(self.state, (ShardedGroupFleet, Mesh2DFleet)):
            raise NotImplementedError(
                "tick_lanes on a meshed fleet — event-stream lanes run the "
                "single placement (TopologySpec()) engines")
        sk = self.state
        items = jnp.asarray(items, jnp.float32)
        if items.shape != (self.num_lanes,):
            raise ValueError(
                f"lane items shape {items.shape} != [{self.num_lanes}]")
        cur = self.cursor
        if not cur.per_lane and mask is not None:
            raise ValueError(
                "tick_lanes(mask=...) needs a per-lane cursor: with the "
                "scalar clock every lane's tick advances together, so a "
                "mask cannot hold individual clocks back — pass NaN items "
                "for no-op lanes, or create the fleet with "
                "per_lane_clock=True")
        if mask is not None:
            mask = jnp.asarray(mask, jnp.int32)
            items = jnp.where(mask == 0, jnp.nan, items)
        prog = self.spec.program
        planes = _lane_tick(
            sk.planes(), cur.t_offset, sk.quantile, items, cur.seed,
            cur.g_offset, self._scalars(),
            program=program_mod.family_base(prog.kernel_family))
        state = sk.with_planes(planes)
        if cur.per_lane:
            if mask is None:
                mask = jnp.where(jnp.isnan(items), 0, 1).astype(jnp.int32)
            cur = cur.advance_lanes(mask)
        else:
            cur = cur.advance(1)
        return dataclasses.replace(self, state=state, cursor=cur)

    def tick_lanes_sparse(self, lanes, items, mask=None, *,
                          donate: bool = False,
                          check_duplicates: bool = False) -> "QuantileFleet":
        """O(events) event round: gather the named lanes, tick them, scatter
        back IN PLACE — a handful of events against millions of lanes never
        does O(L) work (kernels.ops.frugal_update_sparse: the
        donation-aware jitted gather→tick→scatter pair). Requires a
        per-lane cursor; `lanes` must not repeat
        within one call (split same-lane events into successive rounds, in
        arrival order — serve.SLOFleet.flush does exactly this). Lanes with
        mask 0 scatter their own unchanged state back — items there are
        forced to NaN first, so a masked-out slot can never move state
        without advancing the lane's clock — and callers may pad the lane
        list to a stable shape with any lane that has no event this round.

        `donate=True` releases THIS fleet's state buffers to the round so
        the scatters run in place (per-round cost flat in L — the serve
        path's mode); the old fleet object becomes unusable. The default
        keeps functional semantics at the price of one [L] copy per plane.
        `check_duplicates=True` adds an eager host-side round-contract
        check (distinct masked-in lanes; pads off event lanes) — a debug
        aid for new callers, not a hot-path default."""
        if isinstance(self.state, (ShardedGroupFleet, Mesh2DFleet)):
            raise NotImplementedError("tick_lanes_sparse on a meshed fleet")
        if not self.cursor.per_lane:
            raise ValueError("tick_lanes_sparse needs a per-lane cursor "
                             "(create with per_lane_clock=True)")
        sk = self.state
        cur = self.cursor
        lanes = jnp.asarray(lanes, jnp.int32)
        items = jnp.asarray(items, jnp.float32)
        if lanes.shape != items.shape or lanes.ndim != 1:
            raise ValueError(
                f"lanes {lanes.shape} and items {items.shape} must be "
                "matching [K] vectors")
        if check_duplicates:
            _check_sparse_lanes(lanes, items, mask)
        if mask is None:
            mask = jnp.where(jnp.isnan(items), 0, 1).astype(jnp.int32)
        else:
            mask = jnp.asarray(mask, jnp.int32)
            items = jnp.where(mask == 0, jnp.nan, items)
        planes, ticks = kernel_ops.frugal_update_sparse(
            lanes, items, mask, sk.planes(), cur.t_offset, sk.quantile,
            cur.seed, self._scalars(), program=self.spec.program,
            g_offset=cur.g_offset, donate=donate)
        return dataclasses.replace(self, state=sk.with_planes(planes),
                                   cursor=cur._replace(t_offset=ticks))

    def _scalars(self):
        """The spec program's dynamic int32 scalar operands (rule
        parameters) — passed alongside the static family base so parameter
        sweeps share one compiled tick."""
        return tuple(jnp.asarray(v, jnp.int32)
                     for v in self.spec.program.scalar_values())

    # ------------------------------------------------------------------ grow
    def grow_groups(self, num_groups: int,
                    init: Union[float, Array] = 0.0) -> "QuantileFleet":
        """Append groups (capacity growth for dynamic fleets, e.g. serving
        routes). Lane ids are group-major — independent of capacity — so
        growth appends lanes WITHOUT touching any existing lane's state or
        RNG stream (provably: the counter hash keys on absolute lane id)."""
        if num_groups < self.num_groups:
            raise ValueError(f"cannot shrink {self.num_groups} -> {num_groups}")
        if num_groups == self.num_groups:
            return self
        spec = dataclasses.replace(self.spec, num_groups=num_groups)
        fresh = GroupedQuantileSketch.create_lanes(
            num_groups - self.num_groups, spec.quantiles, algo=spec.algo,
            init=init, drift=spec.drift)
        if isinstance(self.state, Mesh2DFleet):
            # Per-replica append: every replica keeps its own lane state
            # bit-for-bit — growth is NOT a sync point (DESIGN.md §15).
            state = self.state.grow(fresh)
        else:
            # Single placement appends in place; a 1-D sharded fleet
            # gathers its real lanes (no merge exists at data=1), appends,
            # and re-shards — pad lanes are re-derived, real lanes ride
            # untouched.
            sk = self._lane_sketch()

            def cat(a, b):
                return None if a is None else jnp.concatenate([a, b])

            grown = dataclasses.replace(
                sk, m=cat(sk.m, fresh.m), step=cat(sk.step, fresh.step),
                sign=cat(sk.sign, fresh.sign),
                m2=cat(sk.m2, fresh.m2), step2=cat(sk.step2, fresh.step2),
                sign2=cat(sk.sign2, fresh.sign2),
                quantile=jnp.concatenate([
                    jnp.broadcast_to(jnp.asarray(sk.quantile, sk.m.dtype),
                                     sk.m.shape),
                    fresh.quantile]))
            state = self._place(spec, grown)
        cur = self.cursor
        if cur.per_lane:
            pad = jnp.zeros((spec.num_lanes - self.num_lanes,), jnp.int32)
            cur = cur._replace(t_offset=jnp.concatenate([cur.t_offset, pad]))
        return QuantileFleet(state=state, cursor=cur, spec=spec)

    # --------------------------------------------------------------- elastic
    def sync(self) -> "QuantileFleet":
        """Fold every data replica through the pinned merge rule and
        broadcast the canonical state back (the DESIGN.md §15 sync point —
        shard_map mode runs the hand-rolled all_gather+fold collective).
        Idempotent, and the identity on single/1-D placements: they hold
        exactly one stream trajectory."""
        if isinstance(self.state, Mesh2DFleet):
            return dataclasses.replace(self, state=self.state.sync())
        return self

    def reshard(self, topology: TopologySpec) -> "QuantileFleet":
        """Re-place this LIVE fleet on `topology` — the elastic topology
        change (grow/shrink the lane fleet, add/remove data replicas,
        collapse to one device) without perturbing existing lanes:

        * same data-replica count: every replica's lane state carries over
          bit-for-bit (pure relayout, no merge);
        * different replica count (including to/from single and 1-D): the
          fleet passes through the pinned merge — a sync point — so
          `estimate()` is invariant and the canonical trajectory continues.

        The cursor is untouched: stream position is placement-independent.
        """
        spec = self.spec.with_topology(topology)
        topo = spec.topology
        if (isinstance(self.state, Mesh2DFleet)
                and topo.placement == "mesh2d"
                and topo.data == self.state.data_replicas):
            old = self.state
            quantile = np.asarray(jax.device_get(
                old.sketch.quantile))[:, :old.num_groups]
            state = Mesh2DFleet.from_replica_planes(
                old.sketch, old.replica_planes(), quantile, topo,
                lanes_per_group=spec.num_quantiles)
        else:
            state = self._place(spec, self._lane_sketch())
        return QuantileFleet(state=state, cursor=self.cursor, spec=spec)

    # ----------------------------------------------------------------- reads
    def query_view(self) -> Tuple[Tuple[np.ndarray, ...], np.ndarray, int,
                                  np.ndarray]:
        """Host-OWNED `(m_planes, t_next, seed, lanes)` — the one gathering
        read behind `estimate()` and repro.service snapshots.

        Only the layout's query planes transfer (a windowed sharded fleet
        moves its two m planes, never the step/sign words), and every array
        is a real `copy=True` host copy: a snapshot taken here can never
        alias a device buffer that a later `tick_lanes_sparse(donate=True)`
        round overwrites in place — the exact bug class an async serve path
        would otherwise hit."""
        prog = self.spec.program
        fields = prog.layout.query_fields
        if isinstance(self.state, Mesh2DFleet):
            # Replicas fold through the pinned merge rule on read; the fold
            # output is host-owned already, np.array(copy=True) for the
            # no-alias guarantee.
            m_planes = tuple(
                np.array(p, dtype=np.float32, copy=True)
                for p in self.state.merged_planes(fields))
        elif isinstance(self.state, ShardedGroupFleet):
            pad = self.state.sketch
            n = self.state.num_groups
            m_planes = tuple(
                np.array(jax.device_get(getattr(pad, f))[:n],
                         dtype=np.float32, copy=True) for f in fields)
        else:
            m_planes = tuple(
                np.array(jax.device_get(getattr(self.state, f)),
                         dtype=np.float32, copy=True) for f in fields)
        cur = self.cursor
        g_off = int(np.asarray(jax.device_get(cur.g_offset)))
        t_next = np.array(jax.device_get(cur.t_offset), dtype=np.int32,
                          copy=True)
        seed = int(np.asarray(jax.device_get(cur.seed)))
        lanes = g_off + np.arange(self.num_lanes, dtype=np.int64)
        return m_planes, t_next, seed, lanes

    def estimate(self, quantile: Optional[float] = None) -> np.ndarray:
        """Current estimates as [G, Q] numpy (the one gathering read); with
        `quantile=` one tracked target's [G] column.

        The spec program's QUERY function answers: vanilla rules return the
        estimate plane, window rules select each lane pair's OLDER plane
        (epoch parity of the lane's absolute tick — a pure function of the
        cursor, not of sketch state), and the 2u-dp rule releases
        Laplace-noised values keyed deterministically on the cursor. Only
        the layout's query planes are gathered — a windowed sharded fleet
        transfers its two m planes, never the step/sign words — and with
        `quantile=` the query runs over that target's lanes alone."""
        prog = self.spec.program
        view = self.query_view()
        if quantile is not None:
            view = quantile_column(
                view, self.num_quantiles,
                self.spec.quantiles.index(float(quantile)))
        m_planes, t_next, seed, lanes = view
        m = np.asarray(prog.run_query(m_planes, t_next=t_next, seed=seed,
                                      lanes=lanes))
        if quantile is not None:
            return m
        return m.reshape(self.num_groups, self.num_quantiles)

    # -------------------------------------------------------- serialization
    def checkpoint_state(self) -> dict:
        """Checkpoint pytree: the lane sketch (stored PACKED — 1-2 words per
        lane, format 4) plus the cursor (int32 leaves). Bit-exact resume:
        restoring and continuing reproduces the uninterrupted trajectory."""
        return {"sketch": self._lane_sketch(), "cursor": self.cursor}

    def checkpoint_template(self) -> dict:
        """Structure-only `like` tree for train.checkpoint.restore_checkpoint
        (abstract leaves; stored shapes win on restore)."""
        return self.template_for(self.spec, per_lane_clock=self.cursor.per_lane)

    @staticmethod
    def template_for(spec: FleetSpec, per_lane_clock: bool = False) -> dict:
        """`checkpoint_template` from a spec alone — no fleet, no array
        allocation (restore of a 2^20-lane fleet should not build one just
        to read shapes off it)."""
        lanes = spec.num_lanes
        f32 = jax.ShapeDtypeStruct((lanes,), jnp.float32)
        i32s = jax.ShapeDtypeStruct((), jnp.int32)
        windowed = spec.program.layout.has_shadow
        m2 = f32 if windowed else None
        if spec.algo == "1u":
            sk = GroupedQuantileSketch(m=f32, step=None, sign=None,
                                       quantile=f32, m2=m2, algo="1u",
                                       drift=spec.drift)
        else:
            sk = GroupedQuantileSketch(m=f32, step=f32, sign=f32,
                                       quantile=f32, m2=m2,
                                       step2=m2, sign2=m2, algo="2u",
                                       drift=spec.drift)
        t_off = jax.ShapeDtypeStruct((lanes,), jnp.int32) \
            if per_lane_clock else i32s
        return {"sketch": sk,
                "cursor": StreamCursor(seed=i32s, t_offset=t_off,
                                       g_offset=i32s)}

    @classmethod
    def from_checkpoint_state(cls, state: dict,
                              spec: FleetSpec) -> "QuantileFleet":
        sk = state["sketch"]
        if sk.num_groups != spec.num_lanes:
            raise ValueError(
                f"checkpoint holds {sk.num_groups} lanes but spec "
                f"{spec.num_groups}x{spec.num_quantiles} expects "
                f"{spec.num_lanes}")
        windowed = spec.program.layout.has_shadow
        if windowed != (sk.m2 is not None):
            raise ValueError(
                f"checkpoint {'has' if sk.m2 is not None else 'lacks'} a "
                f"window shadow plane but spec.drift is {spec.drift!r}")
        if sk.drift != spec.drift:
            # The plane data is drift-parameter-independent; the spec owns
            # the half-life / window length going forward.
            sk = dataclasses.replace(sk, drift=spec.drift)
        cursor = StreamCursor(*(jnp.asarray(x, jnp.int32)
                                for x in state["cursor"]))
        return cls(state=cls._place(spec, sk), cursor=cursor, spec=spec)

    def checkpoint(self, ckpt_dir: str, step: int, keep: int = 3) -> str:
        """Write a committed, per-leaf-checksummed format-4 checkpoint
        (train.checkpoint layout — restore verifies the CRCs and falls back
        to the newest intact step, quarantining corrupt ones).

        The payload is the MERGED canonical lanes (a checkpoint is a sync
        point), so `restore` can re-place it on ANY topology — the manifest
        records the writer's topology as an informational stanza."""
        from repro.train import checkpoint as ckpt
        return ckpt.save_checkpoint(ckpt_dir, step, self.checkpoint_state(),
                                    keep=keep,
                                    topology=self.spec.topology.describe())

    @classmethod
    def restore(cls, ckpt_dir: str, spec: FleetSpec,
                step: Optional[int] = None,
                per_lane_clock: bool = False) -> "QuantileFleet":
        """Load the newest committed checkpoint (or `step`) into a fleet on
        `spec`'s topology — cross-shape restore is free because the payload
        is the canonical merged lanes and every placement shares the
        trajectory (save under (a×b), restore under (c×d), single, or 1-D:
        same bits)."""
        from repro.train import checkpoint as ckpt
        like = cls.template_for(spec, per_lane_clock=per_lane_clock)
        state, _ = ckpt.restore_checkpoint(ckpt_dir, like=like, step=step)
        return cls.from_checkpoint_state(state, spec)
