"""2-D (data × lane) mesh fleets: stream replicas over sharded lanes.

The 1-D lane mesh (group_sharding.py) scales the LANE axis — more groups per
second by giving each device its own lanes. This module adds the DATA axis:
R replicas of the SAME lane fleet, each ingesting a disjoint shard of the
stream, merged on read/sync through a pinned deterministic rule. Together
they form the production (data × lane) topology described by
parallel.topology.TopologySpec and documented in DESIGN.md §15.

Chunk assignment (replica tick-keying)
--------------------------------------
The stream is cut into the same chunk_t blocks every backend uses; chunk
c (absolute tick window [c·chunk_t, (c+1)·chunk_t)) belongs to replica

    replica(c) = c mod R

— a pure function of the ABSOLUTE tick, never of call boundaries. A replica
therefore ingests its chunks at their true absolute offsets, so the counter
RNG (seed, tick, lane) hashes exactly the uniforms a single-device fleet
would for those items: every replica's state is bit-identical to a
single-device fleet that ingested exactly its sub-stream. Calls that start
or end mid-chunk NaN-pad the partial rows (bit-exact no-ops), so any split
of a stream into ingest calls lands every item on the same replica at the
same tick.

Pinned deterministic merge rule (DESIGN.md §15)
-----------------------------------------------
Replica states merge per plane FIELD, by the field's declared invariant
domain (core.program.StateLayout.invariants), as a fixed replica-order
left fold (replica 0 first, ascending):

    finite (estimate heads m/m2): running mean, acc += (x - acc) / (r + 1)
    step   (packed step words):   elementwise max  (stays round-trippable)
    sign   (±1 direction words):  replica 0's value

The fold is order-pinned and uses only IEEE-exact f32 elementwise ops, so
host numpy, the jitted loop fallback, and the shard_map collective all
produce the SAME bits — no psum (whose reduction order is unspecified)
appears anywhere. R = 1 reduces to the identity, and merging already-equal
replicas is the identity, so a sync is idempotent and `estimate()` is
invariant under resharding.

Execution modes
---------------
* shard_map over a real Mesh((data, lanes)) when the topology resolved a
  device tuple — the production path (multi-host via jax.distributed: the
  global device list makes this the same code), zero collectives during
  ingest, one all_gather + pinned fold per sync.
* a sequential Python loop over replicas otherwise (single-device CI) —
  the SAME core.streaming.ingest_slabs body per replica, hence
  bit-identical by construction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import rng as crng
from repro.core import streaming
from repro.core.sketch import GroupedQuantileSketch, PackedSketchState
from repro.resilience import chaos
from .topology import DATA_AXIS, LANE_AXIS, TopologySpec

Array = jax.Array

def pad_lane_fill(layout, field: str) -> float:
    """Dummy state for pad lanes: the program layout's fills, plus the
    quantile plane (not a layout plane — it rides every sketch)."""
    return 0.5 if field == "quantile" else layout.pad_fill(field)


def _sketch_from_planes(program, planes, quantile) -> GroupedQuantileSketch:
    """Assemble a local sketch from a program-ordered plane tuple — the
    inverse of GroupedQuantileSketch.planes()."""
    fields = {"step": None, "sign": None, "m2": None, "step2": None,
              "sign2": None}
    fields.update(zip(program.layout.plane_fields, planes))
    return GroupedQuantileSketch(quantile=quantile, algo=program.algo,
                                 drift=program.drift, **fields)


# --------------------------------------------------------------------------
# The pinned merge rule. ONE implementation over the array namespace (numpy
# on host, jnp under jit / inside shard_map) — the ops are IEEE-exact f32
# elementwise, so every caller produces identical bits.
# --------------------------------------------------------------------------
def _fold_domain(stack, domain: str, xp):
    """Fixed replica-order left fold of stack[R, ...] per invariant domain."""
    r_count = stack.shape[0]
    acc = stack[0]
    if domain == "sign":
        return acc
    for r in range(1, r_count):
        if domain == "finite":
            acc = acc + (stack[r] - acc) / xp.float32(r + 1)
        elif domain == "step":
            acc = xp.maximum(acc, stack[r])
        else:
            raise ValueError(f"unknown invariant domain {domain!r}")
    return acc


def merge_replica_planes(program, planes: Tuple, xp=np) -> Tuple:
    """THE pinned deterministic merge: fold each [R, ...] plane by its
    layout-declared invariant domain (DESIGN.md §15). `xp` selects numpy
    (host) or jax.numpy (device) — bit-identical either way."""
    domains = dict(program.layout.invariants)
    return tuple(_fold_domain(p, domains[f], xp)
                 for f, p in zip(program.layout.plane_fields, planes))


# --------------------------------------------------------------------------
# Jitted entry points, cached per (mesh/topology, program) like the 1-D
# fleet's _sharded_ingest_fn. The ingest body is core.streaming.ingest_slabs
# in BOTH modes — that shared body is the bit-exactness argument.
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _loop_ingest_fn(program):
    @jax.jit
    def fn(planes, quantile, slabs, offsets, seed, g0):
        # planes: tuple of [Gp]; slabs [S_slabs, chunk_t, Gp]; offsets [S].
        sk = _sketch_from_planes(program, planes, quantile)
        sk = streaming.ingest_slabs(sk, slabs, offsets, seed, g0)
        return sk.planes()
    return fn


@functools.lru_cache(maxsize=None)
def _mesh2d_ingest_fn(mesh: Mesh, program, shard_g: int):
    n = program.layout.num_planes
    state_spec = P(DATA_AXIS, LANE_AXIS)

    def body(slabs, offsets, quantile, seed, g0_base, *planes):
        # Per device: slabs [1, S, chunk_t, Gp/lanes], offsets [1, S],
        # quantile/planes [1, Gp/lanes]. The replica index never shifts lane
        # keys — every replica owns the SAME lanes; only the lane shard does.
        g0 = g0_base + jax.lax.axis_index(LANE_AXIS) * shard_g
        sk = _sketch_from_planes(program, tuple(p[0] for p in planes),
                                 quantile[0])
        sk = streaming.ingest_slabs(sk, slabs[0], offsets[0], seed, g0)
        return tuple(p[None] for p in sk.planes())

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(DATA_AXIS, None, None, LANE_AXIS), P(DATA_AXIS, None),
                  state_spec, P(), P()) + (state_spec,) * n,
        out_specs=(state_spec,) * n, check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _slab_fn(mesh: Optional[Mesh]):
    """[T, cols] items -> [R, S, chunk_t, Gp] replica slabs: the Q-fold lane
    fan-out, NaN pad lanes and rows, and the chunk→replica gather of
    Mesh2DFleet._slab_layout. On a mesh the output is laid out
    P(data, -, -, lanes)."""
    def build(items, idx, *, fan, gp, lead, pad_rows, chunk_t):
        if fan > 1:
            items = jnp.repeat(items, fan, axis=1)
        items = jnp.pad(items, ((lead, pad_rows), (0, gp - items.shape[1])),
                        constant_values=jnp.nan)
        chunks = items.reshape(-1, chunk_t, gp)
        slabs = jnp.take(chunks, idx.reshape(-1), axis=0)
        return slabs.reshape(idx.shape + (chunk_t, gp))

    out = None if mesh is None else NamedSharding(
        mesh, P(DATA_AXIS, None, None, LANE_AXIS))
    return jax.jit(build, out_shardings=out,
                   static_argnames=("fan", "gp", "lead", "pad_rows",
                                    "chunk_t"))


@functools.lru_cache(maxsize=None)
def _mesh2d_sync_fn(mesh: Mesh, program):
    """One collective sync: all_gather along the data axis + the pinned
    fold, computed redundantly on every replica so the output IS the synced
    [R, Gp] state (identical rows) — the hand-rolled merge all-reduce (no
    psum: its reduction order is unspecified; the fold's is pinned)."""
    n = program.layout.num_planes
    state_spec = P(DATA_AXIS, LANE_AXIS)
    domains = dict(program.layout.invariants)
    fields = program.layout.plane_fields

    def body(*planes):
        out = []
        for f, p in zip(fields, planes):
            stack = jax.lax.all_gather(p[0], DATA_AXIS)   # [R, Gp/lanes]
            out.append(_fold_domain(stack, domains[f], jnp)[None])
        return tuple(out)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(state_spec,) * n,
                       out_specs=(state_spec,) * n, check_vma=False)
    return jax.jit(fn)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Mesh2DFleet:
    """A lane fleet replicated R ways over a (data × lane) mesh.

    `sketch` holds [R, Gp] leaves — replica-stacked, lane-padded to a
    multiple of the topology's lane-shard count (pad lanes sit at the lane
    tail with dummy state and NaN items, exactly like the 1-D fleet).
    `num_groups` counts REAL lanes. With a device-resolved topology the
    leaves carry NamedSharding(mesh2d, P('data', 'groups')); otherwise they
    are plain arrays driven by the sequential replica loop.

    Replicas drift apart between syncs by design (each sees only its chunk
    shard); `merged()` / `estimate()` answer through the pinned merge rule
    without touching state, and `sync()` broadcasts the merged canonical
    state back to every replica (the topology-change contract's sync
    point — DESIGN.md §15).
    """

    sketch: GroupedQuantileSketch     # [R, Gp] leaves, replica-stacked
    num_groups: int = dataclasses.field(metadata=dict(static=True))
    topology: TopologySpec = dataclasses.field(metadata=dict(static=True))
    lanes_per_group: int = dataclasses.field(metadata=dict(static=True),
                                             default=1)

    # ------------------------------------------------------------ properties
    @property
    def algo(self) -> str:
        return self.sketch.algo

    @property
    def data_replicas(self) -> int:
        return self.topology.data

    @property
    def padded_groups(self) -> int:
        return self.sketch.m.shape[1]

    @property
    def shard_groups(self) -> int:
        return self.padded_groups // self.topology.lanes

    @property
    def mode(self) -> str:
        """'shard_map' (device mesh) or 'loop' (sequential fallback)."""
        return "shard_map" if self.topology.on_devices else "loop"

    def memory_words(self) -> int:
        """Persistent words per lane per REPLICA (the data axis multiplies
        total footprint R-fold — that is the price of stream parallelism)."""
        return self.sketch.memory_words()

    def mesh(self) -> Mesh:
        return self.topology.mesh2d()

    # -------------------------------------------------------------- creation
    @staticmethod
    def from_sketch(sketch: GroupedQuantileSketch,
                    topology: TopologySpec,
                    lanes_per_group: int = 1) -> "Mesh2DFleet":
        """Replicate a canonical [L] sketch across the data axis (every
        replica starts at the canonical state — a sync point)."""
        g = sketch.num_groups
        if g % lanes_per_group:
            raise ValueError(f"sketch lanes {g} not divisible by "
                             f"lanes_per_group={lanes_per_group}")
        r = topology.data
        planes = tuple(
            np.broadcast_to(np.asarray(jnp.broadcast_to(
                jnp.asarray(p, jnp.float32), (g,))), (r, g))
            for p in sketch.planes())
        quantile = np.broadcast_to(
            np.asarray(jnp.broadcast_to(
                jnp.asarray(sketch.quantile, jnp.float32), (g,))), (r, g))
        return Mesh2DFleet._build(sketch, planes, quantile, topology,
                                  lanes_per_group)

    @staticmethod
    def from_replica_planes(like: GroupedQuantileSketch, planes: Tuple,
                            quantile, topology: TopologySpec,
                            lanes_per_group: int = 1) -> "Mesh2DFleet":
        """Re-lay out explicit per-replica [R, L] planes onto `topology`
        (same R) — the elastic relayout path: every replica's lane state is
        carried bit-for-bit, no merge happens."""
        r = topology.data
        for p in planes:
            if p.shape[0] != r:
                raise ValueError(
                    f"replica planes carry R={p.shape[0]} but topology "
                    f"data={r} — resharding across a DIFFERENT replica "
                    "count passes through merged() (a sync point)")
        return Mesh2DFleet._build(like, planes, quantile, topology,
                                  lanes_per_group)

    @staticmethod
    def _build(like: GroupedQuantileSketch, planes: Tuple, quantile,
               topology: TopologySpec,
               lanes_per_group: int) -> "Mesh2DFleet":
        topology = topology.resolve()
        r, g = np.shape(planes[0])
        s = topology.lanes
        gp = -(-g // s) * s
        layout = like.program.layout
        sharding = None
        if topology.on_devices:
            sharding = NamedSharding(topology.mesh2d(), P(DATA_AXIS,
                                                          LANE_AXIS))

        def place(x, field):
            x = jnp.asarray(np.asarray(x, np.float32))
            if gp != g:
                x = jnp.pad(x, ((0, 0), (0, gp - g)),
                            constant_values=pad_lane_fill(layout, field))
            return jax.device_put(x, sharding) if sharding is not None else x

        padded = like.with_planes(
            tuple(place(p, f)
                  for f, p in zip(layout.plane_fields, planes)))
        padded = dataclasses.replace(padded,
                                     quantile=place(quantile, "quantile"))
        return Mesh2DFleet(sketch=padded, num_groups=g, topology=topology,
                           lanes_per_group=lanes_per_group)

    # ---------------------------------------------------------------- ingest
    def _check_items(self, items) -> Tuple[Array, int]:
        """[T, G] group columns (fanned Q-fold), [T, L] lanes, or [T, Gp]
        pre-padded; returns (items, fan-out) — _slab_fn does the fan-out
        and the NaN pad lanes, same contract as the 1-D fleet."""
        items = jnp.asarray(items, jnp.float32)
        if items.ndim == 1:
            items = items[:, None]
        gp = self.padded_groups
        q = self.lanes_per_group
        cols = self.num_groups // q
        ok = {self.num_groups, gp} | ({cols} if q > 1 else set())
        if items.ndim != 2 or items.shape[1] not in ok:
            raise ValueError(f"items shape {items.shape} != [T, {cols}]")
        return items, (q if q > 1 and items.shape[1] == cols else 1)

    def _slab_layout(self, t: int, t0: int, chunk_t: int):
        """Host-side chunk→replica assignment off the ABSOLUTE tick.

        Returns (lead, pad_rows, idx[R, S], offsets[R, S]): the call's items
        are NaN-padded by `lead` rows in front (t0 mod chunk_t — rows of the
        stream's current chunk that earlier calls already applied as real
        rows) and `pad_rows` behind, reshaped to [n_chunks, chunk_t, Gp],
        and chunk j of THIS call goes to replica (c0 + j) mod R where c0 is
        the absolute index of the call's first chunk. idx[r] lists replica
        r's chunk positions in ascending tick order; offsets are the
        absolute (wrapped int32) tick of each slab's row 0."""
        r_count = self.data_replicas
        lead = t0 % chunk_t
        base = t0 - lead
        total = lead + t
        n_chunks = -(-total // chunk_t)
        n_chunks = -(-n_chunks // r_count) * r_count
        pad_rows = n_chunks * chunk_t - total
        c0 = (base // chunk_t) % r_count
        k = np.arange(n_chunks // r_count, dtype=np.int64)
        idx = np.stack([((r - c0) % r_count) + k * r_count
                        for r in range(r_count)])
        # int32 two's-complement wrap (vectorized crng.wrap_i32): the
        # in-kernel tick counter wraps identically, so past-2^31 streams
        # stay chunk-invariant.
        offsets = ((np.asarray(base, np.int64) + idx * chunk_t)
                   & 0xFFFFFFFF).astype(np.uint32).astype(np.int32)
        return lead, pad_rows, idx, offsets

    def ingest_array(self, items, key: Optional[Array] = None,
                     chunk_t: int = 4096, *, seed=None,
                     t_offset: int = 0, g_offset: int = 0) -> "Mesh2DFleet":
        """2-D equivalent of the 1-D fleet's ingest_array: chunks route to
        replicas by absolute tick, each replica scans ITS slabs at their
        true offsets (zero collectives — merge happens only on read/sync).

        `t_offset` must be a host int (the chunk→replica assignment is a
        host-side pure function of the absolute tick); the facade passes
        int(cursor.t_offset). Invariant to how the stream is split into
        calls — a split mid-chunk NaN-pads both sides of the cut, and NaN
        ticks are bit-exact no-ops."""
        if chunk_t <= 0:
            raise ValueError(f"chunk_t must be positive, got {chunk_t}")
        if seed is None:
            assert key is not None, "need key= or seed="
            seed = crng.seed_from_key(key)
        t0 = crng.wrap_i32(int(t_offset))
        items, fan = self._check_items(items)
        t = items.shape[0]
        if t == 0:
            return self
        lead, pad_rows, idx, offsets = self._slab_layout(t, t0, chunk_t)
        layout = dict(fan=fan, gp=self.padded_groups, lead=lead,
                      pad_rows=pad_rows, chunk_t=chunk_t)
        offsets = jnp.asarray(offsets, jnp.int32)
        seed = jnp.asarray(seed, jnp.int32)
        g0 = jnp.asarray(crng.wrap_i32(int(g_offset)), jnp.int32)
        sk = self.sketch
        if self.mode == "shard_map":
            mesh = self.mesh()
            # The slabs are built already sharded: each device makes only
            # its replica's lane shard, never the whole [R, S, chunk_t, Gp].
            items = jax.device_put(items, NamedSharding(mesh, P()))
            slabs = _slab_fn(mesh)(items, idx.astype(np.int32), **layout)
            offsets = jax.device_put(
                offsets, NamedSharding(mesh, P(DATA_AXIS, None)))
            fn = _mesh2d_ingest_fn(mesh, sk.program, self.shard_groups)
            planes = fn(slabs, offsets, sk.quantile, seed, g0, *sk.planes())
        else:
            slabs = _slab_fn(None)(items, idx.astype(np.int32), **layout)
            fn = _loop_ingest_fn(sk.program)
            outs = []
            for r in range(self.data_replicas):
                outs.append(fn(tuple(p[r] for p in sk.planes()),
                               sk.quantile[r], slabs[r], offsets[r],
                               seed, g0))
            planes = tuple(jnp.stack([o[i] for o in outs])
                           for i in range(len(outs[0])))
        return dataclasses.replace(self, sketch=sk.with_planes(planes))

    def ingest_stream(self, chunks: Iterable, key: Optional[Array] = None,
                      chunk_t: int = 4096, *, seed=None, t_offset: int = 0,
                      g_offset: int = 0,
                      skip_items: int = 0) -> "Mesh2DFleet":
        """Host-stream ingest with the crash-consistency contract of the
        other backends: the shared re-chunker yields exact [chunk_t, G]
        blocks — each lands wholly on one replica — and a dying source
        raises a resumable chaos.StreamInterrupted at a chunk boundary."""
        if seed is None:
            assert key is not None, "need key= or seed="
            seed = crng.seed_from_key(key)
        cols = self.num_groups // self.lanes_per_group
        if skip_items:
            chunks = streaming.drop_leading_items(chunks, skip_items, cols)

        consumed = [0]

        def counted(src):
            for c in src:
                c = streaming._as_2d(c, cols)
                consumed[0] += c.shape[0]
                yield c

        fleet = self
        applied = 0
        blocks = streaming.rechunk_blocks(counted(chunks), cols, chunk_t)
        while True:
            try:
                block, rel_t0 = next(blocks)
            except StopIteration:
                break
            except (ValueError, TypeError):
                raise   # malformed input — not resumable
            except Exception as e:
                raise chaos.StreamInterrupted(
                    f"stream source failed after {applied} applied "
                    f"item(s): {e}", state=fleet,
                    items_applied=applied) from e
            fleet = fleet.ingest_array(
                block, seed=seed, chunk_t=chunk_t,
                t_offset=crng.wrap_i32(int(t_offset) + int(rel_t0)),
                g_offset=g_offset)
            applied = min(consumed[0], applied + chunk_t)
            try:
                chaos.count_event("ingest")
            except chaos.StreamFault as e:
                raise chaos.StreamInterrupted(
                    f"stream fault after {applied} applied item(s): {e}",
                    state=fleet, items_applied=applied) from e
        return fleet

    # ----------------------------------------------------------------- reads
    def replica_planes(self) -> Tuple[np.ndarray, ...]:
        """Host [R, L] copies of every layout plane (pad lanes dropped) —
        the bit-preserving view elastic relayout rides on."""
        g = self.num_groups
        return tuple(np.asarray(jax.device_get(p))[:, :g]
                     for p in self.sketch.planes())

    def merged_planes(self, fields: Optional[Tuple[str, ...]] = None
                      ) -> Tuple[np.ndarray, ...]:
        """Host [L] canonical planes through the pinned merge rule. With
        `fields` only those planes gather (estimate moves the query heads,
        never step/sign words)."""
        prog = self.sketch.program
        layout = prog.layout
        fields = layout.plane_fields if fields is None else fields
        g = self.num_groups
        domains = dict(layout.invariants)
        out = []
        for f in fields:
            stack = np.asarray(
                jax.device_get(getattr(self.sketch, f)))[:, :g]
            out.append(_fold_domain(stack, domains[f], np))
        return tuple(out)

    def unshard(self) -> GroupedQuantileSketch:
        """Gather + merge into the canonical host [L] sketch — what
        estimates, health scans, and checkpoints read. (Per-replica state
        is NOT destroyed; see sync() for the broadcast-back.)"""
        merged = self.merged_planes()
        quantile = jnp.asarray(
            np.asarray(jax.device_get(self.sketch.quantile))
            [0, :self.num_groups])
        return _sketch_from_planes(self.sketch.program,
                                   tuple(jnp.asarray(p) for p in merged),
                                   quantile)

    def merged(self) -> GroupedQuantileSketch:
        return self.unshard()

    def estimate(self, t_next=None) -> np.ndarray:
        """Merged per-lane estimates [L] (window rules need the absolute
        tick `t_next`, same as the 1-D fleet — the facade threads it)."""
        prog = self.sketch.program
        m_planes = self.merged_planes(prog.layout.query_fields)
        return prog.run_query(m_planes, t_next=t_next)

    # ------------------------------------------------------------------ sync
    def sync(self) -> "Mesh2DFleet":
        """Broadcast the pinned-merged canonical state back to every
        replica — the sync point the topology-change contract passes
        through. shard_map mode runs the all_gather + fold collective on
        device; loop mode folds on host. Identical bits either way (the
        fold is IEEE-exact f32 elementwise), and idempotent."""
        sk = self.sketch
        if self.mode == "shard_map":
            fn = _mesh2d_sync_fn(self.mesh(), sk.program)
            planes = fn(*sk.planes())
            return dataclasses.replace(self, sketch=sk.with_planes(planes))
        merged = merge_replica_planes(
            sk.program,
            tuple(np.asarray(jax.device_get(p)) for p in sk.planes()))
        r = self.data_replicas
        planes = tuple(jnp.asarray(np.broadcast_to(p, (r,) + p.shape))
                       for p in merged)
        return dataclasses.replace(self, sketch=sk.with_planes(planes))

    # ------------------------------------------------------------------ grow
    def grow(self, fresh: GroupedQuantileSketch) -> "Mesh2DFleet":
        """Append `fresh` lanes (canonical [ΔL] state, e.g. create_lanes) to
        every replica WITHOUT touching existing lanes bit-for-bit: lane ids
        are absolute, so old lanes keep their RNG streams; new lanes start
        identical on all replicas and diverge per replica as chunks arrive,
        exactly as if the fleet had been created at the larger size."""
        planes = self.replica_planes()
        r = self.data_replicas
        fplanes = tuple(
            np.broadcast_to(np.asarray(jnp.broadcast_to(
                jnp.asarray(p, jnp.float32), (fresh.num_groups,))),
                (r, fresh.num_groups))
            for p in fresh.planes())
        grown = tuple(np.concatenate([a, b], axis=1)
                      for a, b in zip(planes, fplanes))
        quantile = np.concatenate([
            np.asarray(jax.device_get(self.sketch.quantile))
            [:, :self.num_groups],
            np.broadcast_to(np.asarray(jnp.broadcast_to(
                jnp.asarray(fresh.quantile, jnp.float32),
                (fresh.num_groups,))), (r, fresh.num_groups))], axis=1)
        like = dataclasses.replace(self.sketch)
        return Mesh2DFleet._build(like, grown, quantile, self.topology,
                                  self.lanes_per_group)

    # -------------------------------------------------------- serialization
    def packed(self) -> PackedSketchState:
        """Checkpoint payload: the MERGED canonical lanes at 1-2 words each
        (a checkpoint is a sync point — DESIGN.md §15), so restore onto ANY
        topology seeds every replica with the same canonical state."""
        return self.unshard().packed()


__all__ = ["DATA_AXIS", "LANE_AXIS", "Mesh2DFleet", "TopologySpec",
           "merge_replica_planes", "pad_lane_fill"]
