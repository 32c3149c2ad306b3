"""Jit'd public wrappers around the program-parameterized Pallas kernel.

ONE blocked/auto entry-point pair serves every registered lane program
(core.program.LaneProgram) — this file used to carry five fused variants
plus four deprecated rand-operand paths; all of them collapsed into:

  * ``frugal_update_blocked(items, planes, quantile, seed, ..., program=)``
    — one padded Pallas dispatch over a [T, G] block. Handles G padding
    (dummy lanes from the layout's fills, dropped on return), T padding
    (NaN items = bit-exact no-op ticks), dtype management, packing the
    plane tuple into the program's serialized words, and interpret-mode
    selection off-TPU.
  * ``frugal_update_auto(items, planes, quantile, ..., program=)`` —
    Pallas on TPU, the jitted program-generic jnp scan elsewhere
    (core.frugal.program_process_seeded); bit-identical results. Accepts a
    JAX PRNG key or a raw int seed; `lanes_per_group` = Q drives a G·Q
    multi-quantile lane plane from G-column items. core.streaming and the
    repro.api backends call this.

Compilation is keyed on ``core.program.family_base(program.family)`` and
rule parameters travel as dynamic int32 scalar operands, so sweeping a
half-life or window length reuses one executable per family.

The removed pre-program entry points (``frugal{1,2}u_update_blocked/_auto``
— the rand[T, G]-operand paths — and the five ``*_fused`` specializations)
remain importable as stubs that raise a ``ValueError`` naming the
replacement (pinned in tests/test_deprecations.py), so stale callers fail
loudly with a migration pointer instead of an ImportError five frames up.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.configs.platform import detect_platform, supports_compiled_kernels
from repro.core import frugal
from repro.core import program as program_mod
from repro.core import rng as crng

from .frugal_update import (
    frugal_program_pallas,
    frugal_program_pallas_dma,
    frugal_program_pallas_gpu,
)

Array = jax.Array

# compiled lowering per platform: Mosaic DMA kernel on TPU, Triton body on
# GPU, the (G, T) revisit grid as the interpret-mode/test workhorse
_PLATFORM_KERNEL = {"tpu": "dma", "gpu": "gpu"}


def _compiled_refusal(entry: str) -> ValueError:
    return ValueError(
        f"{entry}(interpret=False) requests the COMPILED Pallas kernel, but "
        f"the local platform is {detect_platform()!r} — the kernel family "
        "lowers on tpu (Mosaic) and gpu (Triton) only. Pass interpret=True "
        "for the interpret-mode kernel, or use frugal_update_auto(...), "
        "which dispatches the right lowering per platform (with roofline-"
        "autotuned blocks) and the jitted jnp scan elsewhere.")


def _pad_items(items: Array, block_t: int, block_g: int) -> Array:
    t, g = items.shape
    tp = (-t) % block_t
    gp = (-g) % block_g
    if tp or gp:
        items = jnp.pad(items, ((0, tp), (0, gp)), constant_values=jnp.nan)
    return items


def _pad_state(x: Array, block_g: int, fill: float) -> Array:
    g = x.shape[0]
    gp = (-g) % block_g
    if gp:
        x = jnp.pad(x, (0, gp), constant_values=fill)
    return x


# ------------------------------------------------------------------ blocked
@functools.partial(jax.jit,
                   static_argnames=("program", "block_g", "block_t",
                                    "interpret", "kernel"))
def _blocked_jit(items, planes, quantile, seed, scalars, t_offset, g_offset,
                 *, program, block_g, block_t, interpret, kernel):
    layout = program.layout
    g = planes[0].shape[0]
    dt = planes[0].dtype
    items = _pad_items(items.astype(dt), block_t, block_g)
    planes_p = tuple(_pad_state(p, block_g, layout.pad_fill(f))
                     for f, p in zip(layout.plane_fields, planes))
    q_p = _pad_state(jnp.broadcast_to(jnp.asarray(quantile, dt), (g,)),
                     block_g, 0.5)
    words = layout.pack_planes(planes_p)
    common = dict(t_offset=t_offset, g_offset=g_offset, interpret=interpret)
    if kernel == "dma":
        out_words = frugal_program_pallas_dma(
            program, items, words, q_p, seed, scalars, block_g=block_g,
            block_t=block_t, **common)
    elif kernel == "gpu":
        out_words = frugal_program_pallas_gpu(
            program, items, words, q_p, seed, scalars, block_g=block_g,
            **common)
    else:
        out_words = frugal_program_pallas(
            program, items, words, q_p, seed, scalars, block_g=block_g,
            block_t=block_t, **common)
    out = layout.unpack_words(out_words)
    return tuple(p.astype(dt)[:g] for p in out)


def frugal_update_blocked(items, planes, quantile, seed, t_offset=0,
                          g_offset=0, *, program, block_g: int = 128,
                          block_t: int = 256, interpret=True,
                          kernel: str = "grid"):
    """One program-parameterized Pallas dispatch over a [T, G] block.

    `planes` is the program's ordered plane tuple (layout.plane_fields),
    each [G]; returns the updated tuple. `seed` is an int32 counter seed
    (derive from a PRNG key with core.rng.seed_from_key); `t_offset` is the
    absolute stream tick of items[0] so chunked ingestion reproduces the
    unchunked trajectory; `g_offset` the absolute lane index of column 0 so
    a lane-sharded fleet reproduces the single-device trajectory.

    `kernel` picks the lowering ("grid" = the (G, T) revisit grid, "dma" =
    the Mosaic double-buffered DMA path, "gpu" = the Triton body); every
    choice is bit-identical. `interpret` arms: True runs the kernel in
    interpret mode anywhere (the default — this entry point doubles as the
    test harness); False demands the COMPILED lowering and raises a
    ValueError off tpu/gpu instead of crashing in Mosaic; None means
    "compiled where the platform supports it, interpret elsewhere".
    """
    if interpret is None:
        interpret = not supports_compiled_kernels()
    elif interpret is False and not supports_compiled_kernels():
        raise _compiled_refusal("frugal_update_blocked")
    base = program_mod.family_base(program.kernel_family)
    scalars = tuple(jnp.asarray(v, jnp.int32)
                    for v in program.scalar_values())
    return _blocked_jit(items, tuple(planes), quantile,
                        jnp.asarray(seed, jnp.int32), scalars,
                        jnp.asarray(t_offset, jnp.int32),
                        jnp.asarray(g_offset, jnp.int32), program=base,
                        block_g=block_g, block_t=block_t,
                        interpret=bool(interpret), kernel=kernel)


# --------------------------------------------------------------------- auto
def _as_seed(key=None, seed=None):
    if seed is not None:
        return jnp.asarray(seed, jnp.int32)
    assert key is not None, "need key= or seed="
    return crng.seed_from_key(key)


# Jit'd off-TPU dispatch target: core.streaming calls the auto entry point
# once per chunk, and an un-jitted lax.scan would re-trace its tick body on
# every chunk (tens of seconds of pure tracing over a long stream). Runs
# THE program-generic scan — the single jnp transcription of every rule;
# kernels/ref.py stays a test-only oracle. `lanes` is the multi-quantile
# lane fan-out: state is [G·lanes] while items stay [T, G].
@functools.partial(jax.jit, static_argnames=("program", "lanes"))
def _cpu_program(items, planes, quantile, seed, scalars, t_offset, g_offset,
                 *, program, lanes=1):
    out, _ = frugal.program_process_seeded(
        program, planes, items, seed, quantile, scalars=scalars,
        t_offset=t_offset, g_offset=g_offset, lanes_per_group=lanes)
    return out


# --- block override: the test seam proving tuned blocks are pure chunking.
# When active, frugal_update_auto routes through the interpret-mode Pallas
# kernel with the override's (possibly autotuned) blocks even on CPU, so the
# conftest bit-exactness sweep exercises the exact facade path a TPU/GPU
# user gets — different blocking, same trajectory.
_BLOCK_OVERRIDE = None


@contextlib.contextmanager
def block_override(block_g=None, block_t=None, *, autotune_hw=None,
                   kernel: str = "dma"):
    """Force frugal_update_auto through the interpret-mode Pallas `kernel`
    with explicit blocks — or, when `autotune_hw` names an HwSpec (e.g.
    "tpu-v5e"), with blocks the roofline autotuner picks for that hardware.
    Deterministic, so tests can pin tuned-vs-default equality on CPU."""
    global _BLOCK_OVERRIDE
    prev = _BLOCK_OVERRIDE
    _BLOCK_OVERRIDE = dict(block_g=block_g, block_t=block_t,
                           autotune_hw=autotune_hw, kernel=kernel)
    try:
        yield
    finally:
        _BLOCK_OVERRIDE = prev


def _tuned_blocks(program, g_lanes, t, hw=None):
    """(block_g, block_t) from the roofline autotuner; the repo defaults on
    any hardware the registry refuses to price."""
    from repro.roofline.autotune import autotune_blocks

    return autotune_blocks(program, int(g_lanes), int(t), 1, hw=hw)


def frugal_update_auto(items, planes, quantile, key=None, *, seed=None,
                       program, t_offset=0, g_offset=0, lanes_per_group=1,
                       **kw):
    """Program-parameterized fused dispatch: the compiled Pallas lowering
    on TPU (Mosaic, double-buffered item DMA) and GPU (Triton), the jitted
    program scan elsewhere — bit-identical results everywhere.

    On the compiled paths (block_g, block_t) come from the roofline
    autotuner (repro.roofline.autotune, cached per family × layout × hw ×
    shape) unless the caller passes blocks explicitly — zero API change
    for tuned blocks.

    With `lanes_per_group` = Q > 1, `planes`/`quantile` hold G·Q lanes
    while `items` stays [T, G]: the host→device transfer carries only the
    group columns and the Q-fold broadcast happens on device (in the scan
    tick off the compiled paths; as one device-side repeat ahead of the
    Pallas dispatch on them).
    """
    s = _as_seed(key, seed)
    plat = detect_platform()
    ov = _BLOCK_OVERRIDE
    if ov is not None or plat in _PLATFORM_KERNEL:
        if lanes_per_group > 1:
            items = jnp.repeat(items, lanes_per_group, axis=1)
        g_lanes = planes[0].shape[0]
        if ov is not None:
            hw = None
            if ov["autotune_hw"] is not None:
                from repro.roofline.analysis import hw_for
                hw = hw_for(ov["autotune_hw"])
            bg, bt = _tuned_blocks(program, g_lanes, items.shape[0], hw=hw) \
                if hw is not None else (None, None)
            kw.setdefault("block_g", ov["block_g"] or bg or 128)
            kw.setdefault("block_t", ov["block_t"] or bt or 256)
            return frugal_update_blocked(items, planes, quantile, s,
                                         t_offset, g_offset, program=program,
                                         interpret=True, kernel=ov["kernel"],
                                         **kw)
        if "block_g" not in kw or "block_t" not in kw:
            bg, bt = _tuned_blocks(program, g_lanes, items.shape[0])
            kw.setdefault("block_g", bg)
            kw.setdefault("block_t", bt)
        return frugal_update_blocked(items, planes, quantile, s, t_offset,
                                     g_offset, program=program,
                                     interpret=False,
                                     kernel=_PLATFORM_KERNEL[plat], **kw)
    dt = planes[0].dtype
    q = jnp.broadcast_to(jnp.asarray(quantile, dt), planes[0].shape)
    scalars = tuple(jnp.asarray(v, jnp.int32)
                    for v in program.scalar_values())
    return _cpu_program(items.astype(dt), tuple(planes), q, s, scalars,
                        jnp.asarray(t_offset, jnp.int32),
                        jnp.asarray(g_offset, jnp.int32),
                        program=program_mod.family_base(program.kernel_family),
                        lanes=lanes_per_group)


# ------------------------------------------------------------------- sparse
# O(events) event rounds. Two dispatches, by design:
#
#   1. `_sparse_gather_ticks` — a tiny NON-donating jit that gathers the
#      event lanes' clocks.
#   2. `_sparse_scatter[_donated]` — the round itself: gather planes, tick,
#      scatter back. With donation the plane/ticks scatters alias their
#      input buffers and XLA updates them IN PLACE — O(events) work against
#      an [L]-lane fleet.
#
# Why ticks can't be gathered inside step 2: XLA's copy-insertion refuses
# to alias a donated buffer that one op GATHERS from while another op
# SCATTERS into (the scatter lowers to an in-place while-loop whose operand
# must be exclusively owned), so a fused gather+scatter of `ticks` inserts
# a full [L] copy — the exact O(L) pass this path exists to kill. Feeding
# the pre-gathered [K] clocks in leaves `ticks` write-only inside the
# donated executable and the copy vanishes (verified against compiled HLO;
# benchmarks/bench_sparse_ingest.py gates flatness in L). The PLANE buffers
# tolerate the fused gather because their gathers fuse into the [K]-shaped
# tick computation that XLA schedules wholly before the scatters.
@jax.jit
def _sparse_gather_ticks(ticks, lanes):
    return ticks[lanes]


def _sparse_round(lanes, items, mask, planes, ticks, ticks_s, quantile,
                  seed, g_offset, scalars, program):
    """One sparse event round, jnp. Uniforms key on (seed, the lane's own
    pre-gathered tick, absolute lane id) — identical to the dense round, so
    the trajectory is bit-exact with `tick_lanes` on the same events."""
    g_ids = jnp.asarray(g_offset, jnp.int32) + lanes
    q = jnp.asarray(quantile, planes[0].dtype)
    q_s = q[lanes] if q.ndim else jnp.broadcast_to(q, lanes.shape)
    u = crng.counter_uniform(seed, ticks_s, g_ids)
    ctx = frugal.TickCtx(quantile=q_s, t=ticks_s, seed=seed, lanes=g_ids,
                         scalars=scalars)
    out_s = program.run_tick(tuple(p[lanes] for p in planes), items, u, ctx)
    new_planes = tuple(p.at[lanes].set(o) for p, o in zip(planes, out_s))
    new_ticks = ticks.at[lanes].set(ticks_s + mask)
    return new_planes, new_ticks


_sparse_scatter = jax.jit(_sparse_round, static_argnames=("program",))
_sparse_scatter_donated = jax.jit(_sparse_round,
                                  static_argnames=("program",),
                                  donate_argnums=(3, 4))


def frugal_update_sparse(lanes, items, mask, planes, ticks, quantile,
                         seed, scalars=(), *, program, g_offset=0,
                         donate=False):
    """Program-parameterized O(events) event round: gather the `lanes`
    rows of `planes`/`ticks`, tick them once, scatter back.

    `planes` is the program's ordered UNPACKED plane tuple (each [L]),
    `ticks` the per-lane clock [L]; returns the updated (planes, ticks).
    Masked-out slots (mask 0) MUST carry NaN items (repro.api forces this)
    and round-trip their lane bit-exactly — pad with any lane that has no
    masked-in event this round. Masked-in lanes must be distinct.

    `donate=True` hands the caller's plane/tick buffers to XLA for in-place
    scatters — per-round cost flat in L — and INVALIDATES them: only pass
    it when the previous fleet state is dead (serve.SLOFleet's flush loop
    is the intended caller). With donate=False the round stays one fused
    executable but XLA copies each [L] plane to preserve the inputs.

    The round is XLA's own gather/scatter on every platform, TPU included.
    There is no Pallas lowering: Mosaic DMAs a 1-D HBM array only in whole
    1024-element tiles, so a kernel would read, tick and write back one
    tile per event, one event at a time (events sharing a tile would
    race) — two DMA round trips per event.
    """
    base = program_mod.family_base(program.kernel_family)
    scalars = tuple(jnp.asarray(v, jnp.int32) for v in scalars) \
        or tuple(jnp.asarray(v, jnp.int32) for v in program.scalar_values())
    lanes = jnp.asarray(lanes, jnp.int32)
    mask = jnp.asarray(mask, jnp.int32)
    items = jnp.asarray(items, planes[0].dtype)
    seed = jnp.asarray(seed, jnp.int32)
    ticks_s = _sparse_gather_ticks(ticks, lanes)
    step = _sparse_scatter_donated if donate else _sparse_scatter
    return step(lanes, items, mask, tuple(planes), ticks, ticks_s,
                quantile, seed, jnp.asarray(g_offset, jnp.int32), scalars,
                program=base)


# ------------------------------------------------------------ removed paths
_PROGRAM_HINT = ("frugal_update_auto(items, planes, quantile, seed=..., "
                 "program=core.program.make_program(...)) or the "
                 "repro.api.QuantileFleet facade (FleetSpec(program=...))")


def _removed(name: str, why: str):
    def stub(*args, **kwargs):
        raise ValueError(
            f"kernels.ops.{name} was removed by the lane-program engine "
            f"refactor ({why}); use {_PROGRAM_HINT} — see DESIGN.md §11 for "
            "the migration table.")

    stub.__name__ = name
    stub.__qualname__ = name
    stub.__doc__ = (f"REMOVED: {why}. Raises ValueError naming the "
                    "replacement (pinned in tests/test_deprecations.py).")
    return stub


_RAND_WHY = ("the rand[T, G] operand path spent half the hot path's HBM "
             "bandwidth streaming uniforms; uniforms are counter-hashed "
             "on chip now")
_FUSED_WHY = ("the five hand-specialized fused variants collapsed into the "
              "single program-parameterized kernel family")

# Long-deprecated rand-operand entry points (warned since PR 3, removed now).
frugal1u_update_blocked = _removed("frugal1u_update_blocked", _RAND_WHY)
frugal2u_update_blocked = _removed("frugal2u_update_blocked", _RAND_WHY)
frugal1u_update_auto = _removed("frugal1u_update_auto", _RAND_WHY)
frugal2u_update_auto = _removed("frugal2u_update_auto", _RAND_WHY)

# Hand-specialized fused entry points, replaced by the program pair above.
frugal1u_update_blocked_fused = _removed("frugal1u_update_blocked_fused",
                                         _FUSED_WHY)
frugal2u_update_blocked_fused = _removed("frugal2u_update_blocked_fused",
                                         _FUSED_WHY)
frugal1u_update_auto_fused = _removed("frugal1u_update_auto_fused",
                                      _FUSED_WHY)
frugal2u_update_auto_fused = _removed("frugal2u_update_auto_fused",
                                      _FUSED_WHY)
frugal2u_update_blocked_fused_decay = _removed(
    "frugal2u_update_blocked_fused_decay", _FUSED_WHY)
frugal2u_update_auto_fused_decay = _removed(
    "frugal2u_update_auto_fused_decay", _FUSED_WHY)
frugal1u_update_blocked_fused_window = _removed(
    "frugal1u_update_blocked_fused_window", _FUSED_WHY)
frugal1u_update_auto_fused_window = _removed(
    "frugal1u_update_auto_fused_window", _FUSED_WHY)
frugal2u_update_blocked_fused_window = _removed(
    "frugal2u_update_blocked_fused_window", _FUSED_WHY)
frugal2u_update_auto_fused_window = _removed(
    "frugal2u_update_auto_fused_window", _FUSED_WHY)
