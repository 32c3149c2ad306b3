"""ONE Pallas TPU kernel family for every frugal lane program (the hot path).

Pre-program, this file held five hand-specialized fused kernels (vanilla
1U/2U, decayed 2U, windowed 1U/2U) — every new estimator rule cost another
hand-written kernel. Now there is a single kernel body, parameterized by a
``core.program.LaneProgram``: the program's StateLayout fixes the static
state-word count/dtypes and the number of SMEM scalar slots, and the
program's tick function IS the loop body. Registering a new rule in
core/program.py is all it takes to run it on TPU — zero kernel code.

TPU-native layout (see DESIGN.md §3): lanes ride the 128-lane minor
dimension; the serial dependence on m̃ runs as a fori_loop over the T stream
ticks *inside* the kernel while per-lane state stays resident in VMEM.
Uniforms are generated in registers from the counter hash keyed on
(seed, absolute tick, absolute lane) (core.rng, DESIGN.md §4); HBM traffic
is O(T·G·4B) items + O(G·words) state — the bandwidth floor. State crosses
HBM in the program's SERIALIZED words: each (m, step, sign) plane-pair is
m [f32] + ONE packed int32 (core.packing), so a 2U program moves exactly
the paper's two words per lane, a windowed 2U program two words per plane.

Scalar-prefetch operand: ``[3 + len(layout.scalar_names)]`` int32 —
(seed, t_offset, g_offset, *program scalars). Rule parameters (decay alpha
bits, window length, ...) are DYNAMIC operands: sweeping them never
recompiles, and the same compiled kernel serves every instance of a family
(kernels/ops.py keys compilation on ``core.program.family_base``).

Grid: (G_blocks, T_blocks). The T dimension is a sequential revisit of the
same state block ("arbitrary" semantics); the G dimension is parallel.
State blocks are [1, BG] 2-D tiles (TPU prefers >=2-D); item blocks [BT, BG].

Padding contract (see ops.py): G is padded with the layout's dummy state
(lanes dropped on return); T is padded with NaN items — NaN compares False
in both directions, so a padded tick is a bit-exact no-op. The hash keys on
absolute indices, so padding never perturbs the uniforms consumed by real
ticks and results are invariant to block shape and chunk boundaries.

Quantile is a [1, G] VMEM operand (not SMEM scalar) so per-lane targets are
supported for free — a fleet can track q50 for some lanes and q99 for
others in one call (the repro.api multi-quantile lane plane relies on it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import frugal
from repro.core import rng as crng
from repro.roofline import kernel_model

Array = jax.Array


def _compiler_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _lane_ids(g_blk, block_g, g0):
    """Absolute lane index per VPU lane, [1, block_g] int32. Every value in
    a kernel body stays 2-D: Mosaic refuses rank-1 vectors. `g0` is the
    fleet-global index of array column 0 — nonzero when this call ingests
    one shard of a lane-sharded fleet (parallel/group_sharding.py), so
    every shard hashes uniforms at the same (seed, t, lane) keys as the
    unsharded fleet."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, block_g), 1)
    return g0 + g_blk * block_g + iota


def _program_kernel(seed_ref, q_ref, items_ref, *state_refs, program,
                    block_t, block_g):
    """THE kernel body. ``state_refs`` is the program's serialized word
    list twice over: layout.num_words inputs then the same many outputs.
    The body unpacks words to planes ONCE per (G, T) block, runs the
    program's tick over the block's ticks with on-chip uniforms, and
    repacks — identical expressions to the jnp scan, hence bit-identical
    trajectories."""
    layout = program.layout
    nw = layout.num_words
    in_refs, out_refs = state_refs[:nw], state_refs[nw:]
    g_blk = pl.program_id(0)
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _seed():
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[...] = i_ref[...]

    q = q_ref[...]
    seed = seed_ref[0]
    t0 = seed_ref[1] + t_blk * block_t          # absolute stream tick of row 0
    g_ids = _lane_ids(g_blk, block_g, seed_ref[2])
    scalars = tuple(seed_ref[3 + k] for k in range(len(layout.scalar_names)))

    planes0 = layout.unpack_words(tuple(r[...] for r in out_refs))

    def body(i, planes):
        it = items_ref[pl.ds(i, 1), :]
        r = crng.counter_uniform(seed, t0 + i, g_ids)
        ctx = frugal.TickCtx(quantile=q, t=t0 + i, seed=seed, lanes=g_ids,
                             scalars=scalars)
        return program.run_tick(planes, it, r, ctx)

    planes = jax.lax.fori_loop(0, block_t, body, planes0)
    for r, w in zip(out_refs, layout.pack_planes(planes)):
        r[...] = w


def _program_kernel_dma(seed_ref, q_ref, items_hbm, *refs, program,
                        block_t, block_g, n_chunks):
    """The REAL-TPU lowering of the dense body: grid (G_blocks,) only, state
    planes resident in VMEM for the WHOLE stream, items double-buffer-DMA'd
    HBM→VMEM one [block_t, block_g] tile ahead of the tick loop.

    The (G, T)-grid kernel above round-trips every state word through HBM at
    each T-block revisit — fine in interpret mode, but on hardware it is
    exactly the traffic the paper says we don't need to pay. Here the items
    operand stays in memory-space ANY (never blocked through the pipeline);
    chunk ci+1's DMA is issued before chunk ci is consumed, so the tick
    loop hides the item transfer and state crosses HBM exactly once.
    Same tick expressions, same absolute (seed, tick, lane) uniform keys —
    bit-identical to the grid kernel and the jnp scan (pinned by the
    conftest sweep in interpret mode, where make_async_copy is emulated).

    ``refs`` = num_words input refs, num_words output refs, then the two
    scratch refs: items VMEM [2, block_t, block_g] and a DMA semaphore [2].
    """
    layout = program.layout
    nw = layout.num_words
    in_refs, out_refs = refs[:nw], refs[nw:2 * nw]
    scratch, sem = refs[2 * nw], refs[2 * nw + 1]
    gi = pl.program_id(0)

    def item_dma(slot, ci):
        return pltpu.make_async_copy(
            items_hbm.at[pl.ds(ci * block_t, block_t),
                         pl.ds(gi * block_g, block_g)],
            scratch.at[slot], sem.at[slot])

    item_dma(0, 0).start()

    q = q_ref[...]
    seed = seed_ref[0]
    g_ids = _lane_ids(gi, block_g, seed_ref[2])
    scalars = tuple(seed_ref[3 + k] for k in range(len(layout.scalar_names)))
    planes0 = layout.unpack_words(tuple(r[...] for r in in_refs))

    def chunk(ci, planes):
        slot = jax.lax.rem(ci, 2)

        @pl.when(ci + 1 < n_chunks)
        def _prefetch():
            item_dma(jax.lax.rem(ci + 1, 2), ci + 1).start()

        item_dma(slot, ci).wait()
        t0 = seed_ref[1] + ci * block_t

        def body(i, pls):
            it = scratch[slot, pl.ds(i, 1), :]
            r = crng.counter_uniform(seed, t0 + i, g_ids)
            ctx = frugal.TickCtx(quantile=q, t=t0 + i, seed=seed,
                                 lanes=g_ids, scalars=scalars)
            return program.run_tick(pls, it, r, ctx)

        return jax.lax.fori_loop(0, block_t, body, planes)

    planes = jax.lax.fori_loop(0, n_chunks, chunk, planes0)
    for r, w in zip(out_refs, layout.pack_planes(planes)):
        r[...] = w


def _program_kernel_gpu(meta_ref, q_ref, items_ref, *state_refs, program,
                        t_total, block_g):
    """The Triton/GPU lowering of the SAME body. CUDA grid cells are
    parallel CTAs with no sequential-revisit semantics, so the (G, T) grid
    of the TPU kernel is invalid here: the grid is (G_blocks,) and the full
    T loop runs in-kernel. Triton refs are lazy GMEM pointer views, so the
    per-tick row load ``items_ref[pl.ds(i, 1), :]`` reads [1, block_g]
    floats straight from HBM (L2-cached across the warp) — no DMA
    choreography to write.
    PrefetchScalarGridSpec is TPU-only, so the meta vector rides as a
    regular [1, n] operand. No pltpu symbol is touched on this path, which
    also makes it interpret-testable on CPU."""
    layout = program.layout
    nw = layout.num_words
    in_refs, out_refs = state_refs[:nw], state_refs[nw:]
    g_blk = pl.program_id(0)

    q = q_ref[...]
    seed = meta_ref[0, 0]
    t0 = meta_ref[0, 1]
    g_ids = _lane_ids(g_blk, block_g, meta_ref[0, 2])
    scalars = tuple(meta_ref[0, 3 + k]
                    for k in range(len(layout.scalar_names)))
    planes0 = layout.unpack_words(tuple(r[...] for r in in_refs))

    def body(i, planes):
        it = items_ref[pl.ds(i, 1), :]
        r = crng.counter_uniform(seed, t0 + i, g_ids)
        ctx = frugal.TickCtx(quantile=q, t=t0 + i, seed=seed, lanes=g_ids,
                             scalars=scalars)
        return program.run_tick(planes, it, r, ctx)

    planes = jax.lax.fori_loop(0, t_total, body, planes0)
    for r, w in zip(out_refs, layout.pack_planes(planes)):
        r[...] = w


def _kernel_name(program, lowering: str) -> str:
    """The `pallas_call` name: XLA names the kernel's custom-call op after
    it, so a device trace says which kernel and family ran (on a TPU the
    dense DMA kernel of `2u` is `%frugal_2u_dma.<n>`)."""
    return f"frugal_{program.family.replace('-', '_')}_{lowering}"


def _seed_operand(seed, t_offset, g_offset, scalars=()) -> Array:
    """[3 + n] int32 scalar-prefetch operand: (counter seed, stream tick
    offset, fleet-global lane offset, *program scalar slots)."""
    parts = [jnp.asarray(seed, jnp.int32),
             jnp.asarray(t_offset, jnp.int32),
             jnp.asarray(g_offset, jnp.int32)]
    parts += [jnp.asarray(s, jnp.int32) for s in scalars]
    return jnp.stack(parts)


def frugal_program_pallas(
    program,          # core.program.LaneProgram (STATIC — compile key;
                      # callers pass family_base so parameter sweeps share
                      # one executable)
    items: Array,     # [T, G] float32 (NaN = no-op tick)
    words,            # layout.num_words state words, each [G]
    quantile: Array,  # [G] float32 (per-lane targets supported)
    seed,             # int32 scalar — counter RNG seed
    scalars=(),       # program's int32 scalar operands (dynamic)
    *,
    t_offset=0,       # absolute stream tick of items[0] (chunked ingest)
    g_offset=0,       # absolute lane index of column 0 (sharded fleets)
    block_g: int = 128,
    block_t: int = 256,
    interpret: bool = False,
):
    """One grouped frugal ingest dispatch for ANY registered lane program.

    Shapes must be pre-padded: T % block_t == 0, G % block_g == 0 (ops.py
    handles padding & unpadding). Returns the updated word tuple, each [G].
    Bit-identical to core.frugal.program_process_seeded for the same
    (program, seed, offsets) and invariant to block shape / chunking /
    lane sharding (absolute-index RNG keys).
    """
    layout = program.layout
    t, g = items.shape
    assert t % block_t == 0 and g % block_g == 0, (t, g, block_t, block_g)
    assert len(words) == layout.num_words, (len(words), layout.num_words)
    grid = (g // block_g, t // block_t)

    state_spec = pl.BlockSpec((1, block_g), lambda gi, ti, *_: (0, gi))
    stream_spec = pl.BlockSpec((block_t, block_g), lambda gi, ti, *_: (ti, gi))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[state_spec, stream_spec] + [state_spec] * layout.num_words,
        out_specs=[state_spec] * layout.num_words,
    )
    outs = pl.pallas_call(
        functools.partial(_program_kernel, program=program, block_t=block_t,
                          block_g=block_g),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((1, g), dt)
                   for dt in layout.word_dtypes],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=_kernel_name(program, "grid"),
    )(_seed_operand(seed, t_offset, g_offset, scalars), quantile[None, :],
      items, *[w[None, :] for w in words])
    return tuple(o[0] for o in outs)


def frugal_program_pallas_dma(
    program,          # core.program.LaneProgram (STATIC — compile key)
    items: Array,     # [T, G] float32 (NaN = no-op tick), stays in HBM
    words,            # layout.num_words state words, each [G]
    quantile: Array,  # [G] float32
    seed,
    scalars=(),
    *,
    t_offset=0,
    g_offset=0,
    block_g: int = 128,
    block_t: int = 256,
    interpret: bool = False,
):
    """The Mosaic/TPU lowering with double-buffered item DMA — the path
    `frugal_update_auto` compiles on real TPUs (and the autotuner tunes).

    Contract identical to frugal_program_pallas (pre-padded shapes,
    absolute-index RNG, updated word tuple back), but the grid is
    (G_blocks,) with "parallel" semantics only: state planes load into
    VMEM once, the whole T stream ticks against them, items arrive via
    the 2-slot DMA pipeline in _program_kernel_dma. Interpret mode
    emulates the DMA, so the bit-exactness sweep covers this path on CPU.
    """
    layout = program.layout
    t, g = items.shape
    assert t % block_t == 0 and g % block_g == 0, (t, g, block_t, block_g)
    assert len(words) == layout.num_words, (len(words), layout.num_words)
    n_chunks = t // block_t

    state_spec = pl.BlockSpec((1, block_g), lambda gi, *_: (0, gi))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g // block_g,),
        in_specs=[state_spec, any_spec] + [state_spec] * layout.num_words,
        out_specs=[state_spec] * layout.num_words,
        scratch_shapes=[
            pltpu.VMEM((2, block_t, block_g), items.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(_program_kernel_dma, program=program,
                          block_t=block_t, block_g=block_g,
                          n_chunks=n_chunks),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((1, g), dt)
                   for dt in layout.word_dtypes],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=kernel_model.VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=_kernel_name(program, "dma"),
    )(_seed_operand(seed, t_offset, g_offset, scalars), quantile[None, :],
      items, *[w[None, :] for w in words])
    return tuple(o[0] for o in outs)


def frugal_program_pallas_gpu(
    program,          # core.program.LaneProgram (STATIC — compile key)
    items: Array,     # [T, G] float32 (NaN = no-op tick)
    words,            # layout.num_words state words, each [G]
    quantile: Array,  # [G] float32
    seed,
    scalars=(),
    *,
    t_offset=0,
    g_offset=0,
    block_g: int = 128,
    interpret: bool = False,
):
    """The Triton/GPU lowering of the dense body (see _program_kernel_gpu).

    Contract identical to frugal_program_pallas except there is no
    block_t: each of the G_blocks CTAs runs the full T loop in-kernel
    (CUDA grids have no sequential-revisit semantics, so a T grid axis
    cannot exist here). Requires G % block_g == 0 only. No pltpu symbols,
    so interpret mode runs this exact path on CPU."""
    layout = program.layout
    t, g = items.shape
    assert g % block_g == 0, (g, block_g)
    assert len(words) == layout.num_words, (len(words), layout.num_words)
    n_meta = 3 + len(layout.scalar_names)

    state_spec = pl.BlockSpec((1, block_g), lambda gi: (0, gi))
    outs = pl.pallas_call(
        functools.partial(_program_kernel_gpu, program=program, t_total=t,
                          block_g=block_g),
        grid=(g // block_g,),
        in_specs=[pl.BlockSpec((1, n_meta), lambda gi: (0, 0)),
                  state_spec,
                  pl.BlockSpec((t, block_g), lambda gi: (0, gi))]
        + [state_spec] * layout.num_words,
        out_specs=[state_spec] * layout.num_words,
        out_shape=[jax.ShapeDtypeStruct((1, g), dt)
                   for dt in layout.word_dtypes],
        interpret=interpret,
        name=_kernel_name(program, "gpu"),
    )(_seed_operand(seed, t_offset, g_offset, scalars)[None, :],
      quantile[None, :], items, *[w[None, :] for w in words])
    return tuple(o[0] for o in outs)
