"""Group-axis sharding: scale the fleet's G axis past one device.

The paper's GROUPBY setting makes groups embarrassingly parallel — every
group's trajectory depends only on its own items and its own counter-hashed
uniforms. This module shards the [G] state axis of a GroupedQuantileSketch
across a 1-D device mesh with shard_map, so chunked ingest dispatches one
fused kernel per shard with ZERO cross-device traffic: no collective appears
anywhere in the ingest path (frugal sketches have no merge operator, and
none is needed — each device owns its groups outright). Only `estimate()` /
`unshard()` gather, and only when read.

Bit-exactness contract (the spec, tested in tests/test_group_sharding.py):
because the counter RNG keys uniforms on the ABSOLUTE (seed, tick, group)
triple (core.rng, DESIGN.md §4), a shard that knows the fleet-global index
of its column 0 (`g_offset = axis_index * shard_size`) hashes exactly the
uniforms the unsharded fleet would — so any mesh shape, any chunking, and
any ragged-G padding reproduce the single-device trajectory bit-for-bit.

Ragged G: the fleet pads its lanes up to a multiple of mesh size × lanes
per group, so every shard holds whole groups. Pad lanes sit at the global
tail (real groups keep their absolute indices), carry dummy state, and
receive NaN items — a bit-exact no-op tick — then are dropped on read.
The counter hash is stateless, so pad lanes "consuming" uniforms at tail
keys perturbs nothing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Iterable, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import rng as crng
from repro.core import streaming
from repro.core.drift import is_windowed as drift_is_windowed
from repro.core.sketch import GroupedQuantileSketch, PackedSketchState
from repro.resilience import chaos
from .mesh2d import pad_lane_fill

Array = jax.Array

GROUP_AXIS = "groups"


def group_mesh(num_devices: Optional[int] = None,
               axis_name: str = GROUP_AXIS) -> Mesh:
    """1-D mesh over the first `num_devices` devices (all by default)."""
    devs = jax.devices()
    n = num_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"group_mesh needs {n} devices, found {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis_name,))


# Pad-lane dummy state now lives in mesh2d.pad_lane_fill (both meshes pad
# lanes the same way); the old private name stays as an alias for callers.
_pad_lane_fill = pad_lane_fill


def _sketch_from_planes(program, planes, quantile) -> GroupedQuantileSketch:
    """Assemble a local (per-shard) sketch from a program-ordered plane
    tuple — the inverse of GroupedQuantileSketch.planes()."""
    fields = {"step": None, "sign": None, "m2": None, "step2": None,
              "sign2": None}
    fields.update(zip(program.layout.plane_fields, planes))
    return GroupedQuantileSketch(quantile=quantile, algo=program.algo,
                                 drift=program.drift, **fields)


# One jitted shard_map per (mesh, program, shard width, chunking, fan-out) —
# cached so repeated ingest calls hit the same compiled executable. Meshes
# hash by device list + axis names, so a fleet reuses its entry across
# calls. The ONE body's operand width derives from the program's
# StateLayout — a 1U fleet moves one plane, a windowed 2U fleet six; no
# placeholder [Gp] arrays ever ride along (e9 gates the vanilla hot path's
# scaling). Each shard takes its own [T, Gp/(n·Q)] group columns and runs
# the single-device `streaming.ingest_array` on them, so the Q-fold
# group→lane fan-out happens on every device inside the same code the
# unsharded fleet runs. The jitted function is named `sharded_ingest`: its
# executable is `jit_sharded_ingest` in a profile.
@functools.lru_cache(maxsize=None)
def _sharded_ingest_fn(mesh: Mesh, axis: str, program, shard_g: int,
                       chunk_t: int, lanes_per_group: int):
    n = program.layout.num_planes
    state_spec = P(axis)

    def sharded_ingest(items, quantile, seed, t0, g0_base, *planes):
        # g0_base shifts every shard when THIS WHOLE FLEET is itself a
        # column slice of a larger one (the facade cursor's g_offset).
        g0 = g0_base + jax.lax.axis_index(axis) * shard_g
        local = _sketch_from_planes(program, planes, quantile)
        out = streaming.ingest_array(local, items, seed=seed, chunk_t=chunk_t,
                                     g_offset=g0, t_offset=t0,
                                     lanes_per_group=lanes_per_group)
        return out.planes()

    fn = jax.shard_map(
        sharded_ingest, mesh=mesh,
        in_specs=(P(None, axis), state_spec, P(), P(), P())
        + (state_spec,) * n,
        out_specs=(state_spec,) * n, check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _stack_rows_fn(mesh: Mesh, axis: str):
    """T [Gp] rows cut over `axis` by column, stacked into the [T, Gp]
    chunk cut the same way: each device stacks its own pieces."""
    def stack_rows(*rows):
        return jnp.stack(rows)

    return jax.jit(stack_rows,
                   out_shardings=NamedSharding(mesh, P(None, axis)))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGroupFleet:
    """A GroupedQuantileSketch whose G axis lives sharded on a device mesh.

    `sketch` holds globally-shaped [Gp] leaves placed with
    NamedSharding(mesh, P('groups')) where Gp = ceil(G / mesh.size) ·
    mesh.size; `num_groups` is the real (unpadded) G. All ingest entry
    points are bit-identical to the unsharded single-device path.

    When the sketch is a multi-quantile lane plane (`lanes_per_group` = Q >
    1, see GroupedQuantileSketch.create_lanes / repro.api.QuantileFleet),
    the FLATTENED lane axis is what shards: `num_groups` counts real lanes,
    a shard's `g_offset` is its absolute lane offset, and ingest takes
    [T, G] group columns. Lanes pad to a multiple of mesh size × Q, so each
    shard holds whole groups: `_pad_items` places each shard's group
    columns on its own device (`item_sharding`), and every shard fans its
    columns out Q-fold itself. The counter RNG keys on absolute lane ids,
    so estimates are invariant to how lanes land on devices.

    Registered as a pytree (sketch leaves dynamic, layout static) so a
    fleet can ride inside jitted steps and checkpoint pytrees.
    """

    sketch: GroupedQuantileSketch     # padded [Gp] leaves, device-placed
    num_groups: int = dataclasses.field(metadata=dict(static=True))
    mesh: Mesh = dataclasses.field(metadata=dict(static=True))
    axis: str = dataclasses.field(metadata=dict(static=True),
                                  default=GROUP_AXIS)
    lanes_per_group: int = dataclasses.field(metadata=dict(static=True),
                                             default=1)

    # ------------------------------------------------------------ properties
    @property
    def algo(self) -> str:
        return self.sketch.algo

    @property
    def padded_groups(self) -> int:
        return self.sketch.num_groups

    @property
    def shard_groups(self) -> int:
        return self.sketch.num_groups // self.mesh.shape[self.axis]

    @property
    def item_sharding(self) -> NamedSharding:
        """Where ingest wants its [T, Gp/Q] padded group columns: split by
        column over the mesh, each shard's columns on its own device."""
        return NamedSharding(self.mesh, P(None, self.axis))

    def memory_words(self) -> int:
        """Persistent words per group — 1 (1U) or 2 (2U), same as unsharded."""
        return self.sketch.memory_words()

    # -------------------------------------------------------------- creation
    @staticmethod
    def create(num_groups: int,
               quantile: Union[float, Array] = 0.5,
               algo: str = "2u",
               init: Union[float, Array] = 0.0,
               mesh: Optional[Mesh] = None,
               axis: str = GROUP_AXIS,
               drift=None) -> "ShardedGroupFleet":
        mesh = mesh if mesh is not None else group_mesh(axis_name=axis)
        sk = GroupedQuantileSketch.create(num_groups, quantile=quantile,
                                          algo=algo, init=init, drift=drift)
        return ShardedGroupFleet.from_sketch(sk, mesh, axis=axis)

    @staticmethod
    def from_sketch(sketch: GroupedQuantileSketch, mesh: Optional[Mesh] = None,
                    axis: str = GROUP_AXIS,
                    lanes_per_group: int = 1) -> "ShardedGroupFleet":
        """Shard an existing (host / single-device) sketch across `mesh`.

        `lanes_per_group` marks the sketch as a (G × Q) lane plane whose
        flattened lane axis is being sharded; ingest then accepts [T, G]
        group columns (see class docstring)."""
        mesh = mesh if mesh is not None else group_mesh(axis_name=axis)
        g = sketch.num_groups
        if g % lanes_per_group:
            raise ValueError(f"sketch lanes {g} not divisible by "
                             f"lanes_per_group={lanes_per_group}")
        # Whole groups per shard: pad lanes to a multiple of n·Q.
        unit = mesh.shape[axis] * lanes_per_group
        gp = -(-g // unit) * unit
        sharding = NamedSharding(mesh, P(axis))

        layout = sketch.program.layout

        def place(x, field):
            x = jnp.broadcast_to(jnp.asarray(x, jnp.float32), (g,))
            if gp != g:
                x = jnp.pad(x, (0, gp - g),
                            constant_values=_pad_lane_fill(layout, field))
            return jax.device_put(x, sharding)

        padded = sketch.with_planes(
            tuple(place(p, f)
                  for f, p in zip(layout.plane_fields, sketch.planes())))
        padded = dataclasses.replace(padded,
                                     quantile=place(sketch.quantile,
                                                    "quantile"))
        return ShardedGroupFleet(sketch=padded, num_groups=g, mesh=mesh,
                                 axis=axis, lanes_per_group=lanes_per_group)

    # ---------------------------------------------------------------- ingest
    def _pad_items(self, items, shard_span=contextlib.nullcontext) -> Array:
        """[T, G] group columns, padded with NaN columns to the mesh's
        multiple and placed by `item_sharding`. Host columns go to their
        shards row by row (`_stage_rows`), never whole onto one device;
        `shard_span()` wraps the wait for each device's rows. Idempotent: an
        array already padded and placed (a pre-placed benchmark input, a
        chunk the ingest pipeline staged) passes through as it is."""
        if not isinstance(items, jax.Array):
            items = np.asarray(items, np.float32)
        if items.ndim == 1:
            items = items[:, None]
        cols = self.num_groups // self.lanes_per_group
        padded = self.padded_groups // self.lanes_per_group
        if items.ndim != 2 or items.shape[1] not in (cols, padded):
            raise ValueError(
                f"items shape {items.shape} != [T, {cols}]")
        pad = ((0, 0), (0, padded - items.shape[1]))
        if isinstance(items, jax.Array):
            items = items.astype(jnp.float32)
            if pad[1][1]:  # pad lanes get NaN items: bit-exact no-ops
                items = jnp.pad(items, pad, constant_values=jnp.nan)
            return jax.device_put(items, self.item_sharding)
        if pad[1][1]:
            items = np.pad(items, pad, constant_values=np.nan)
        return self._stage_rows(np.ascontiguousarray(items), shard_span)

    def _stage_rows(self, items: np.ndarray, shard_span) -> Array:
        """Row-major host `items` placed by `item_sharding`, returning once
        every device holds its slab. A device's [T, Gp/n] column slab is T
        contiguous row pieces of the host array: each is sent as it lies in
        host memory, and the device stacks them, so no transfer reads a
        strided host view. On a TPU v5e 2x2, a 4 GiB chunk sent as strided
        slabs reached its chips in 0.17 s in some processes and 1.6-1.8 s
        in others; as row pieces, in 0.18 s in every run so far (PERF.md
        section 5; benchmarks/stage_probe.py times both)."""
        width = items.shape[1]
        rows = NamedSharding(self.mesh, P(self.axis))
        sent = {device: jax.device_put([row[cols] for row in items], device)
                for device, (cols,) in
                rows.addressable_devices_indices_map((width,)).items()}
        for pieces in sent.values():
            with shard_span():
                jax.block_until_ready(pieces)
        by_row = [jax.make_array_from_single_device_arrays(
            (width,), rows, [pieces[r] for pieces in sent.values()])
            for r in range(items.shape[0])]
        return jax.block_until_ready(
            _stack_rows_fn(self.mesh, self.axis)(*by_row))

    def _run_sharded(self, items: Array, seed, t0, chunk_t: int,
                     g_offset=0) -> "ShardedGroupFleet":
        sk = self.sketch
        fn = _sharded_ingest_fn(self.mesh, self.axis, sk.program,
                                self.shard_groups, chunk_t,
                                self.lanes_per_group)
        scalars = (jnp.asarray(seed, jnp.int32), jnp.asarray(t0, jnp.int32),
                   jnp.asarray(g_offset, jnp.int32))
        planes = fn(items, sk.quantile, *scalars, *sk.planes())
        return dataclasses.replace(self, sketch=sk.with_planes(planes))

    def ingest_array(self, items, key: Optional[Array] = None,
                     chunk_t: int = 4096, *, seed=None,
                     t_offset: int = 0,
                     g_offset: int = 0) -> "ShardedGroupFleet":
        """Sharded equivalent of core.streaming.ingest_array: every device
        scans its own [chunk_t, G/n] slabs; no collectives. Bit-identical to
        the unsharded call for the same key. `t_offset` is the absolute
        stream tick of items[0] — pass the running total when continuing a
        stream across calls, otherwise a same-seed second call would replay
        the first call's uniforms. `g_offset` shifts every shard's lane keys
        when this whole fleet is a column slice of a larger one (same
        meaning as the unsharded entry points)."""
        if chunk_t <= 0:
            raise ValueError(f"chunk_t must be positive, got {chunk_t}")
        if seed is None:
            assert key is not None, "need key= or seed="
            seed = crng.seed_from_key(key)
        return self._run_sharded(self._pad_items(items), seed,
                                 crng.wrap_i32(t_offset), chunk_t,
                                 crng.wrap_i32(g_offset))

    def ingest_stream(self, chunks: Iterable, key: Optional[Array] = None,
                      chunk_t: int = 4096, *, seed=None, t_offset: int = 0,
                      g_offset: int = 0,
                      skip_items: int = 0) -> "ShardedGroupFleet":
        """Sharded equivalent of core.streaming.ingest_stream: the same host
        re-chunker (identical blocking), one sharded fused dispatch per
        [chunk_t, G] block. `t_offset` continues an earlier stream's tick
        counter and `g_offset` shifts the fleet's lane keys (see
        ingest_array). Crash-consistent with the same contract as the core
        entry point: a dying source raises a resumable
        chaos.StreamInterrupted whose `state` is the fleet advanced through
        every fully-applied chunk, and `skip_items=err.items_applied`
        replays only the uncommitted suffix, bit-exact."""
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
                block, t0 = next(blocks)
            except StopIteration:
                break
            except (ValueError, TypeError):
                raise   # malformed input — not resumable
            except Exception as e:
                raise chaos.StreamInterrupted(
                    f"stream source failed after {applied} applied "
                    f"item(s): {e}", state=fleet,
                    items_applied=applied) from e
            fleet = fleet._run_sharded(fleet._pad_items(block), seed,
                                       crng.wrap_i32(t_offset + t0), chunk_t,
                                       crng.wrap_i32(g_offset))
            applied = min(consumed[0], applied + chunk_t)
            try:
                chaos.count_event("ingest")
            except chaos.StreamFault as e:
                raise chaos.StreamInterrupted(
                    f"stream fault after {applied} applied item(s): {e}",
                    state=fleet, items_applied=applied) from e
        return fleet

    # ----------------------------------------------------------------- reads
    def estimate(self, t_next=None) -> np.ndarray:
        """Current per-group estimates [G] — the one gathering read.

        Layout-driven: only the program's query planes are gathered (a
        windowed fleet transfers its two m planes, never the step/sign
        words). A windowed fleet answers from the OLDER plane of each
        lane's pair, which is a function of the absolute stream tick: pass
        `t_next` (items ingested so far — what a facade cursor carries) or
        use repro.api.QuantileFleet, which threads it for you. Reading a
        windowed fleet without the tick would silently return the
        just-restarted plane half the epochs, so the program's query
        raises instead."""
        sk = self.sketch
        n = self.num_groups
        prog = sk.program
        m_planes = tuple(np.asarray(jax.device_get(getattr(sk, f)))[:n]
                         for f in prog.layout.query_fields)
        return prog.run_query(m_planes, t_next=t_next)

    def unshard(self) -> GroupedQuantileSketch:
        """Gather the fleet back into a host-resident unsharded sketch."""
        g = self.num_groups

        def take(x):
            return jnp.asarray(np.asarray(jax.device_get(x))[:g])

        sk = self.sketch

        def take_opt(x):
            return None if x is None else take(x)

        if self.algo == "1u":
            return GroupedQuantileSketch(m=take(sk.m), step=None, sign=None,
                                         quantile=take(sk.quantile),
                                         m2=take_opt(sk.m2), algo="1u",
                                         drift=sk.drift)
        return GroupedQuantileSketch(m=take(sk.m), step=take(sk.step),
                                     sign=take(sk.sign),
                                     quantile=take(sk.quantile),
                                     m2=take_opt(sk.m2),
                                     step2=take_opt(sk.step2),
                                     sign2=take_opt(sk.sign2), algo="2u",
                                     drift=sk.drift)

    # -------------------------------------------------------- serialization
    def packed(self) -> PackedSketchState:
        """Checkpoint payload: 1-2 words per REAL group (pad lanes dropped)."""
        return self.unshard().packed()

    @staticmethod
    def from_packed(p: PackedSketchState, mesh: Optional[Mesh] = None,
                    axis: str = GROUP_AXIS,
                    drift=None) -> "ShardedGroupFleet":
        """`drift` must restate the fleet's DriftConfig: the packed payload
        carries plane DATA only (a decay fleet is layout-identical to
        vanilla, and a shadow plane names no window length), so omitting it
        restores vanilla lanes / default-W windows. Refuses a shadow-plane
        mismatch rather than guessing."""
        has_shadow = getattr(p, "m2", None) is not None
        if has_shadow != drift_is_windowed(drift):
            raise ValueError(
                f"packed payload {'has' if has_shadow else 'lacks'} a window "
                f"shadow plane but drift={drift!r} — pass the fleet's "
                "original DriftConfig")
        return ShardedGroupFleet.from_sketch(
            GroupedQuantileSketch.from_packed(p, drift=drift), mesh,
            axis=axis)

    def state_shardings(self):
        """NamedSharding pytree matching `packed()` — feed to
        train.checkpoint.restore_checkpoint(shardings=...) to re-place a
        saved fleet directly onto this mesh (elastic restore)."""
        sh = NamedSharding(self.mesh, P(self.axis))
        layout = self.sketch.program.layout
        shadow = layout.has_shadow
        paired = self.algo != "1u"
        return PackedSketchState(
            m=sh, step_sign=sh if paired else None, quantile=sh,
            m2=sh if shadow else None,
            step_sign2=sh if shadow and paired else None)
