"""LaneProgram — the rule-driven update core behind every frugal backend.

The paper's estimator is a tiny per-item state transition: 1-2 words per
lane, one compare/select bundle per tick. Before this module, each RULE
(vanilla 1U, vanilla 2U, decayed 2U, windowed 1U, windowed 2U) was
transcribed separately per BACKEND — its own jnp scan branch, its own fused
Pallas kernel, its own blocked/auto entry point, its own shard_map body
width. Adding an estimator variant cost O(backends) hand-written kernels.

A `LaneProgram` collapses that matrix to one axis. It is:

  * a pure per-lane **tick** — ``tick(program, planes, item, uniform, ctx)
    -> planes`` — written once in plain jnp, executed verbatim by the
    lax.scan engine (core.frugal.program_process_seeded), inside the ONE
    Pallas kernel body (kernels/frugal_update._program_kernel), and inside
    the shard_map ingest body (parallel/group_sharding). ``ctx`` is a
    core.frugal.TickCtx carrying (quantile, absolute tick, seed, absolute
    lane ids, int32 scalar operands) — everything a rule may key on.
  * a static **StateLayout**: the ordered plane fields the rule persists,
    how they pack into serialized/kernel words (each (m, step, sign)
    plane-pair packs to m + one int32 via core.packing — the paper's "two
    units of memory plus a bit", literally), which planes answer queries,
    and which extra int32 scalar slots ride the kernels' SMEM
    scalar-prefetch operand.
  * a **query** — ``query(program, m_planes, t_next, seed, lanes)`` — the
    host-side read: vanilla rules return the estimate plane, the window
    rules select the older plane from the cursor's epoch parity, and the
    DP rule adds calibrated reporting noise.

Every registered program is bit-exact across backend x chunking x mesh by
construction: uniforms key on the absolute (seed, tick, lane) triple
(core.rng, DESIGN.md §4) and the tick maths is literally the same jnp
expression tree everywhere. New rules cost ONE tick function and ONE layout
— zero backend-specific code (DESIGN.md §11 has the plane-layout table).

Registered families:

  name        algo  planes                              scalar slots
  ----------  ----  ----------------------------------  --------------------
  1u          1u    (m,)                                ()
  2u          2u    (m, step, sign)                     ()
  2u-decay    2u    (m, step, sign)                     (alpha_bits, floor_bits)
  1u-window   1u    (m, m2)                             (window,)
  2u-window   2u    (m, step, sign, m2, step2, sign2)   (window,)
  2u-dp       2u    (m, step, sign)                     ()   [query-noised]

``2u-dp`` is the proof the abstraction pays: the output-perturbation DP
variant in the spirit of Cafaro et al. (*Space-Efficient Private Estimation
of Quantiles*, 2025). Its tick IS the registered vanilla 2U tick (the same
function object — zero new kernel code, it even shares the compiled 2U
kernel), and privacy lives entirely in the query: each released estimate is
m + Laplace(1/epsilon) noise, derived DETERMINISTICALLY from the counter
hash at (seed ^ salt, t_next, lane) so reports are replayable and invariant
to backend/chunking/mesh like everything else. (Per-release epsilon under
the unit-sensitivity convention of frugal updates — each item moves the
estimate by O(1); see the Cafaro et al. analysis for composition.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from . import frugal
from . import packing
from . import rng as crng
from . import drift as drift_mod
from .drift import DriftConfig

Array = jax.Array

# Salt for the DP reporting-noise stream: keeps query-time draws disjoint
# from every ingest-time uniform (which key on the raw seed).
_DP_SALT = int(np.int32(np.uint32(0x5DEECE66).view(np.int32)))
# Lanes per pass of the DP release: 64K lanes keep its 512 KiB float64
# temporaries in a core's cache.
_DP_BLOCK = 1 << 16


# Plane-invariant domains resilience.health knows how to check. Every
# registered layout must assign one to each plane field (validate_program).
_INVARIANT_DOMAINS = ("finite", "step", "sign")


# ---------------------------------------------------------------- StateLayout
@dataclasses.dataclass(frozen=True)
class StateLayout:
    """Static shape of a program's persistent state.

    plane_fields — ordered GroupedQuantileSketch field names the program
                   persists; the engine's plane tuples follow this order.
    packing      — serialization/kernel-word spec: one (head, pair) unit per
                   plane-pair, where `head` is the f32 estimate plane and
                   `pair` is an optional (step, sign) pair packed into ONE
                   int32 word (core.packing). Word count == memory words
                   per lane, the paper's accounting.
    scalar_names — extra int32 operands beyond the base (seed, t_offset,
                   g_offset) triple; they ride the kernels' SMEM
                   scalar-prefetch slots and the scan's ctx.scalars, so a
                   rule parameter sweep never recompiles.
    query_fields — estimate planes a read must gather (the window rules
                   need both heads to pick the older plane).
    invariants   — (field, domain) health declarations, one per plane
                   field: 'finite' (estimate heads), 'step' (finite AND
                   value-round-trips through the packed word), 'sign'
                   (exactly ±1). resilience.health.validate_planes derives
                   its vectorized corruption check from these, so a
                   program only gets self-healing if it declares them —
                   validate_program refuses registration otherwise.
    """

    plane_fields: Tuple[str, ...]
    packing: Tuple[Tuple[str, Optional[Tuple[str, str]]], ...]
    scalar_names: Tuple[str, ...] = ()
    query_fields: Tuple[str, ...] = ("m",)
    invariants: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        flat = []
        for head, pair in self.packing:
            flat.append(head)
            if pair is not None:
                flat.extend(pair)
        if tuple(flat) != self.plane_fields:
            raise ValueError(
                f"packing spec {self.packing} does not enumerate "
                f"plane_fields {self.plane_fields} in order")
        if not set(self.query_fields) <= set(self.heads):
            raise ValueError(
                f"query_fields {self.query_fields} must be packing heads "
                f"{self.heads}")
        seen = set()
        for field, domain in self.invariants:
            if field not in self.plane_fields:
                raise ValueError(
                    f"invariant declared for unknown plane field {field!r} "
                    f"(plane_fields {self.plane_fields})")
            if domain not in _INVARIANT_DOMAINS:
                raise ValueError(
                    f"invariant domain {domain!r} for plane {field!r} is not "
                    f"one of {_INVARIANT_DOMAINS}")
            if field in seen:
                raise ValueError(
                    f"duplicate invariant declaration for plane {field!r}")
            seen.add(field)

    # ------------------------------------------------------------ properties
    @property
    def heads(self) -> Tuple[str, ...]:
        """The f32 estimate plane of each plane-pair."""
        return tuple(h for h, _ in self.packing)

    @property
    def has_shadow(self) -> bool:
        """True when the program carries a second plane-pair (window rules) —
        THE dispatch predicate layers used to spell `is_windowed(drift)`."""
        return len(self.packing) > 1

    @property
    def num_planes(self) -> int:
        return len(self.plane_fields)

    @property
    def word_dtypes(self):
        """Serialized/kernel word dtypes, unit-major: f32 head [+ i32 pair]."""
        dts = []
        for _, pair in self.packing:
            dts.append(jnp.float32)
            if pair is not None:
                dts.append(jnp.int32)
        return tuple(dts)

    @property
    def num_words(self) -> int:
        """Persistent memory words per lane — the paper's footprint claim."""
        return len(self.word_dtypes)

    def pad_fill(self, field: str) -> float:
        """Dummy-state fill for padded lanes (same values every layer uses)."""
        return 0.0 if field in self.heads else 1.0

    # ------------------------------------------------------- word conversion
    def pack_planes(self, planes) -> Tuple[Array, ...]:
        """Plane tuple -> serialized word tuple (f32 head + packed i32 pair
        per unit). Pure jnp — runs inside the Pallas kernel body too."""
        by_field = dict(zip(self.plane_fields, planes))
        words = []
        for head, pair in self.packing:
            words.append(by_field[head])
            if pair is not None:
                words.append(packing.pack_step_sign(by_field[pair[0]],
                                                    by_field[pair[1]]))
        return tuple(words)

    def unpack_words(self, words) -> Tuple[Array, ...]:
        """Bit-exact inverse of pack_planes (in-domain step magnitudes)."""
        planes = []
        wi = 0
        for _, pair in self.packing:
            planes.append(words[wi])
            wi += 1
            if pair is not None:
                step, sign = packing.unpack_step_sign(words[wi])
                wi += 1
                planes.extend((step, sign))
        return tuple(planes)


# ----------------------------------------------------------------- LaneProgram
@dataclasses.dataclass(frozen=True)
class LaneProgram:
    """One frugal update rule, executable by every backend.

    Hashable (frozen dataclass; tick/query/trace are module-level functions)
    so a program rides as static pytree metadata, a jit static argument, and
    an lru_cache key. Two programs built from the same family + parameters
    compare equal, so spec equality and jit caches behave.
    """

    family: str                     # registry name, e.g. "2u-window"
    algo: str                       # base comparison rule: "1u" | "2u"
    layout: StateLayout
    tick: Callable                  # (prog, planes, item, u, ctx) -> planes
    query: Callable                 # (prog, m_planes, t_next, seed, lanes)
    trace: Callable                 # (prog, planes, t_abs) -> [L] jnp trace row
    drift: Optional[DriftConfig] = None   # decay/window parameter carrier
    dp_epsilon: Optional[float] = None    # 2u-dp reporting-noise budget

    # -------------------------------------------------------------- execution
    def run_tick(self, planes, item, u, ctx) -> Tuple[Array, ...]:
        return tuple(self.tick(self, planes, item, u, ctx))

    def run_query(self, m_planes, t_next=None, seed=None, lanes=None):
        if self.layout.has_shadow and t_next is None:
            raise ValueError(
                f"{self.family}: estimate() needs t_next (absolute items "
                "ingested) to select the older window plane — read through "
                "repro.api.QuantileFleet, whose cursor carries it")
        return self.query(self, m_planes, t_next, seed, lanes)

    def run_trace(self, planes, t_abs) -> Array:
        return self.trace(self, planes, t_abs)

    # ------------------------------------------------------------- descriptors
    @property
    def kernel_family(self) -> str:
        """Family whose compiled kernel/scan this program executes. The DP
        rule's tick IS the vanilla 2U tick, so it shares the 2U executable —
        'zero program-specific kernel code', literally."""
        return "2u" if self.family == "2u-dp" else self.family

    def scalar_values(self) -> Tuple[int, ...]:
        """int32 values for layout.scalar_names, resolved from this
        instance's parameters. Dynamic operands: sweeping a half-life or a
        window length never recompiles a kernel."""
        vals = []
        for name in self.layout.scalar_names:
            if name == "alpha_bits":
                vals.append(int(self.drift.alpha_bits))
            elif name == "floor_bits":
                vals.append(int(self.drift.floor_bits))
            elif name == "window":
                vals.append(int(self.drift.window))
            else:  # pragma: no cover - registration error
                raise ValueError(f"{self.family}: unknown scalar slot {name!r}")
        return tuple(vals)

    def memory_words(self) -> int:
        return self.layout.num_words


# ------------------------------------------------------------- tick functions
# Each is the SINGLE transcription of its rule: the scan engine, the Pallas
# kernel body, and the facade's event-lane ticks all run these exact
# expressions, which is what makes cross-backend agreement bit-exact by
# construction rather than by test luck.
def _tick_1u(prog, planes, item, u, ctx):
    (m,) = planes
    st = frugal.frugal1u_update(frugal.Frugal1UState(m), item, u, ctx.quantile)
    return (st.m,)


def _tick_2u(prog, planes, item, u, ctx):
    st = frugal.frugal2u_update(frugal.Frugal2UState(*planes), item, u,
                                ctx.quantile)
    return (st.m, st.step, st.sign)


def _tick_2u_decay(prog, planes, item, u, ctx):
    # alpha/floor arrive as f32 BIT PATTERNS in int32 scalar slots (SMEM on
    # TPU) and are bitcast back here, so every backend multiplies by the
    # identical float. The bitcast runs on a vector of the item's shape:
    # Mosaic has no scalar bitcast.
    shape = jnp.shape(item)
    alpha, floor = (
        jax.lax.bitcast_convert_type(jnp.broadcast_to(s, shape), jnp.float32)
        for s in ctx.scalars[:2])
    st = drift_mod.decay2u_update(frugal.Frugal2UState(*planes), item, u,
                                  ctx.quantile, alpha, floor)
    return (st.m, st.step, st.sign)


def _tick_window(prog, planes, item, u, ctx):
    w = ctx.scalars[0]
    if prog.algo == "1u":
        m, m2 = planes
        one = jnp.ones_like(m)
        st = drift_mod.window_update(
            drift_mod.WindowState(m=m, step=one, sign=one, m2=m2, step2=one,
                                  sign2=one), item, u, ctx.quantile, ctx.t, w,
            algo="1u")
        return (st.m, st.m2)
    st = drift_mod.window_update(drift_mod.WindowState(*planes), item, u,
                                 ctx.quantile, ctx.t, w, algo="2u")
    return tuple(st)


# ------------------------------------------------------------ query functions
def _query_head(prog, m_planes, t_next, seed, lanes):
    return np.asarray(m_planes[0])


def _query_window(prog, m_planes, t_next, seed, lanes):
    m, m2 = (np.asarray(p) for p in m_planes)
    primary = drift_mod.query_plane_is_primary(np.asarray(t_next),
                                               prog.drift.window)
    return np.where(primary, m, m2)


def _query_dp(prog, m_planes, t_next, seed, lanes):
    """Laplace-noised reporting: estimate + Lap(1/epsilon), with the noise
    a pure function of (seed ^ salt, t_next, lane). Same stream position ->
    same released value, on every backend. Host numpy alone: the inputs are
    host copies, so the release makes no JAX call and no transfer. It runs
    `_DP_BLOCK` lanes at a time, so its float64 temporaries stay in cache
    rather than streaming through host memory that ingest staging shares;
    every lane's arithmetic is the same, so the bits are too."""
    if seed is None or t_next is None or lanes is None:
        raise ValueError(
            "2u-dp: noised reporting needs the stream cursor (seed, t_next, "
            "lane ids) — read through repro.api.QuantileFleet")
    m, t, lanes = np.asarray(m_planes[0]), np.asarray(t_next), \
        np.asarray(lanes)
    key = int(seed) ^ _DP_SALT
    scale = 1.0 / float(prog.dp_epsilon)
    out = np.empty(m.shape, np.float32)
    for i in range(0, m.shape[0], _DP_BLOCK):
        b = slice(i, i + _DP_BLOCK)
        u = crng.counter_uniform_host(key, t[b] if t.ndim else t,
                                      lanes[b]).astype(np.float64)
        centered = u - 0.5
        noise = -scale * np.sign(centered) * np.log(
            np.maximum(1.0 - 2.0 * np.abs(centered),
                       np.finfo(np.float64).tiny))
        out[b] = (np.asarray(m[b], np.float64) + noise).astype(np.float32)
    return out


# ------------------------------------------------------------ trace functions
def _trace_head(prog, planes, t_abs):
    return planes[0]


def _trace_window(prog, planes, t_abs):
    # After processing tick t_abs the stream holds t_abs+1 items; trace the
    # plane a query would answer from (the one NOT restarted this epoch).
    w = jnp.int32(prog.drift.window)
    epoch = jnp.asarray(t_abs, jnp.int32) // w
    primary = epoch - (epoch // 2) * 2 == 1
    m2 = planes[prog.layout.plane_fields.index("m2")]
    return jnp.where(primary, planes[0], m2)


# ----------------------------------------------------------------- registry
_L_1U = StateLayout(plane_fields=("m",), packing=(("m", None),),
                    invariants=(("m", "finite"),))
_L_2U = StateLayout(plane_fields=("m", "step", "sign"),
                    packing=(("m", ("step", "sign")),),
                    invariants=(("m", "finite"), ("step", "step"),
                                ("sign", "sign")))
# dataclasses.replace inherits _L_2U's invariants — derived layouts keep
# their health coverage without restating it.
_L_2U_DECAY = dataclasses.replace(_L_2U,
                                  scalar_names=("alpha_bits", "floor_bits"))
_L_1U_WINDOW = StateLayout(plane_fields=("m", "m2"),
                           packing=(("m", None), ("m2", None)),
                           scalar_names=("window",),
                           query_fields=("m", "m2"),
                           invariants=(("m", "finite"), ("m2", "finite")))
_L_2U_WINDOW = StateLayout(
    plane_fields=("m", "step", "sign", "m2", "step2", "sign2"),
    packing=(("m", ("step", "sign")), ("m2", ("step2", "sign2"))),
    scalar_names=("window",),
    query_fields=("m", "m2"),
    invariants=(("m", "finite"), ("step", "step"), ("sign", "sign"),
                ("m2", "finite"), ("step2", "step"), ("sign2", "sign")))


def _refuse_params(family, **kw):
    extra = [k for k, v in kw.items() if v is not None]
    if extra:
        raise ValueError(f"program {family!r} takes no {extra} parameter(s)")


def _build_1u(half_life=None, floor=None, window=None, epsilon=None,
              drift=None):
    _refuse_params("1u", half_life=half_life, floor=floor, window=window,
                   epsilon=epsilon, drift=drift)
    return LaneProgram(family="1u", algo="1u", layout=_L_1U, tick=_tick_1u,
                       query=_query_head, trace=_trace_head)


def _build_2u(half_life=None, floor=None, window=None, epsilon=None,
              drift=None):
    _refuse_params("2u", half_life=half_life, floor=floor, window=window,
                   epsilon=epsilon, drift=drift)
    return LaneProgram(family="2u", algo="2u", layout=_L_2U, tick=_tick_2u,
                       query=_query_head, trace=_trace_head)


def _build_2u_decay(half_life=None, floor=None, window=None, epsilon=None,
                    drift=None):
    _refuse_params("2u-decay", window=window, epsilon=epsilon)
    if drift is None:
        drift = DriftConfig(mode="decay",
                            half_life=4096 if half_life is None else half_life,
                            floor=0.0 if floor is None else floor)
    elif drift.mode != "decay":
        raise ValueError(f"2u-decay needs a decay DriftConfig, got {drift!r}")
    return LaneProgram(family="2u-decay", algo="2u", layout=_L_2U_DECAY,
                       tick=_tick_2u_decay, query=_query_head,
                       trace=_trace_head, drift=drift)


def _build_window(algo):
    family = f"{algo}-window"
    layout = _L_1U_WINDOW if algo == "1u" else _L_2U_WINDOW

    def build(half_life=None, floor=None, window=None, epsilon=None,
              drift=None):
        _refuse_params(family, half_life=half_life, floor=floor,
                       epsilon=epsilon)
        if drift is None:
            drift = DriftConfig(mode="window",
                                window=4096 if window is None else window)
        elif drift.mode != "window":
            raise ValueError(
                f"{family} needs a window DriftConfig, got {drift!r}")
        return LaneProgram(family=family, algo=algo, layout=layout,
                           tick=_tick_window, query=_query_window,
                           trace=_trace_window, drift=drift)

    return build


def _build_2u_dp(half_life=None, floor=None, window=None, epsilon=None,
                 drift=None):
    _refuse_params("2u-dp", half_life=half_life, floor=floor, window=window,
                   drift=drift)
    epsilon = 1.0 if epsilon is None else float(epsilon)
    if not epsilon > 0.0:
        raise ValueError(f"2u-dp epsilon must be positive, got {epsilon}")
    # The tick is the SAME function object as the vanilla 2U rule: the DP
    # mechanism is pure output perturbation, so ingest shares 2U's kernels.
    return LaneProgram(family="2u-dp", algo="2u", layout=_L_2U, tick=_tick_2u,
                       query=_query_dp, trace=_trace_head,
                       dp_epsilon=epsilon)


_FAMILIES = {
    "1u": _build_1u,
    "2u": _build_2u,
    "2u-decay": _build_2u_decay,
    "1u-window": _build_window("1u"),
    "2u-window": _build_window("2u"),
    "2u-dp": _build_2u_dp,
}


def registered_families() -> Tuple[str, ...]:
    return tuple(_FAMILIES)


def make_program(family, *, half_life=None, floor=None, window=None,
                 epsilon=None, drift=None) -> LaneProgram:
    """Build a program instance by family name (the `program=` spelling of
    repro.api.FleetSpec). Passing an existing LaneProgram returns it."""
    if isinstance(family, LaneProgram):
        return family
    if family not in _FAMILIES:
        raise ValueError(f"unknown lane program {family!r}; registered: "
                         f"{', '.join(_FAMILIES)}")
    return _FAMILIES[family](half_life=half_life, floor=floor, window=window,
                             epsilon=epsilon, drift=drift)


@functools.lru_cache(maxsize=None)
def family_base(family: str) -> LaneProgram:
    """Canonical default-parameter instance — the compile key for kernels and
    jitted scans: rule parameters travel as dynamic scalar operands, so every
    instance of a family shares one executable."""
    return make_program(family)


@functools.lru_cache(maxsize=None)
def program_for(algo: str, drift: Optional[DriftConfig] = None,
                dp_epsilon: Optional[float] = None) -> LaneProgram:
    """Map the legacy (algo=, drift=) spelling onto its program (DESIGN.md
    §11 migration table). This is how pre-program sketches/fleets dispatch."""
    if dp_epsilon is not None:
        if algo != "2u" or drift is not None:
            raise ValueError("the DP rule is 2u-only and drift-free")
        return make_program("2u-dp", epsilon=dp_epsilon)
    if drift is None:
        return family_base(algo)
    if drift.mode == "decay":
        drift.validate_for_algo(algo)
        return make_program("2u-decay", drift=drift)
    return make_program(f"{algo}-window", drift=drift)


def test_instances() -> Tuple[LaneProgram, ...]:
    """One canonical small-parameter instance per registered family — what
    the shared bit-exactness harness (tests/conftest.py) and the program
    lint (repro.api.lint) sweep. Registering a family here is what buys a
    new rule its backend x chunking x mesh coverage for free."""
    return (
        make_program("1u"),
        make_program("2u"),
        make_program("2u-decay", half_life=48),
        make_program("1u-window", window=96),
        make_program("2u-window", window=96),
        make_program("2u-dp", epsilon=0.5),
    )


# ------------------------------------------------------------------ validation
def validate_program(prog: LaneProgram) -> None:
    """Registration lint: a half-registered program must fail CI, not a user.

    Checks the packing spec enumerates the planes, the scalar slots resolve
    and match the tick's scan signature (a smoke tick runs with exactly
    len(scalar_names) operands), the tick preserves plane arity/dtypes, the
    words round-trip, and the query answers. Called per registered family by
    repro.api.lint (CI step) and tests/test_public_api.py (tier-1).
    """
    layout = prog.layout  # __post_init__ already validated field coverage
    if prog.algo not in ("1u", "2u"):
        raise AssertionError(f"{prog.family}: algo {prog.algo!r}")

    # Health coverage: every plane field must declare an invariant domain,
    # or resilience.health cannot validate (and so cannot self-heal) this
    # program's lanes. Heads/query planes must be 'finite' — a query must
    # never read a plane the health check would not flag on NaN/inf.
    inv = dict(layout.invariants)
    missing_inv = [f for f in layout.plane_fields if f not in inv]
    if missing_inv:
        raise AssertionError(
            f"{prog.family}: plane field(s) {missing_inv} declare no "
            "invariant domain — add invariants=((field, domain), ...) to the "
            "StateLayout so resilience.health.validate_planes covers them")
    for f in layout.heads:
        if inv[f] != "finite":
            raise AssertionError(
                f"{prog.family}: estimate head {f!r} must declare the "
                f"'finite' invariant, not {inv[f]!r}")
    vals = prog.scalar_values()
    if len(vals) != len(layout.scalar_names):
        raise AssertionError(
            f"{prog.family}: {len(layout.scalar_names)} declared scalar "
            f"slot(s) but scalar_values() resolves {len(vals)}")
    if not all(isinstance(v, int) for v in vals):
        raise AssertionError(f"{prog.family}: scalar slots must be int32 "
                             f"values, got {vals}")

    # Smoke tick: 2 lanes, one real + one NaN item — the scan signature.
    n = 2
    planes = tuple(
        jnp.full((n,), layout.pad_fill(f), jnp.float32)
        for f in layout.plane_fields)
    ctx = frugal.TickCtx(
        quantile=jnp.full((n,), 0.5, jnp.float32),
        t=jnp.int32(0), seed=jnp.int32(1),
        lanes=jnp.arange(n, dtype=jnp.int32),
        scalars=tuple(jnp.asarray(max(v, 1), jnp.int32) for v in vals))
    item = jnp.asarray([3.0, jnp.nan], jnp.float32)
    u = jnp.full((n,), 0.25, jnp.float32)
    out = prog.run_tick(planes, item, u, ctx)
    if len(out) != layout.num_planes:
        raise AssertionError(
            f"{prog.family}: tick returned {len(out)} plane(s), layout "
            f"declares {layout.num_planes}")
    for f, p in zip(layout.plane_fields, out):
        if jnp.shape(p) != (n,) or p.dtype != jnp.float32:
            raise AssertionError(
                f"{prog.family}: tick output plane {f!r} has "
                f"shape {jnp.shape(p)} dtype {p.dtype}")

    words = layout.pack_planes(out)
    if len(words) != layout.num_words:
        raise AssertionError(f"{prog.family}: packing spec word count")
    for w, dt in zip(words, layout.word_dtypes):
        if w.dtype != dt:
            raise AssertionError(f"{prog.family}: word dtype {w.dtype} != {dt}")
    back = layout.unpack_words(words)
    for f, a, b in zip(layout.plane_fields, out, back):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(
                f"{prog.family}: plane {f!r} does not round-trip its words")

    m_planes = tuple(np.zeros((n,), np.float32) for _ in layout.query_fields)
    est = prog.run_query(m_planes, t_next=1, seed=0,
                         lanes=np.arange(n, dtype=np.int32))
    if np.shape(est) != (n,):
        raise AssertionError(f"{prog.family}: query shape {np.shape(est)}")

    tr = prog.run_trace(out, jnp.int32(0))
    if jnp.shape(tr) != (n,):
        raise AssertionError(f"{prog.family}: trace shape {jnp.shape(tr)}")


def validate_registry() -> Tuple[str, ...]:
    """Validate every registered family's canonical instance; returns the
    family names checked (for lint reporting).

    test_instances() must cover the WHOLE registry: it is also what the
    shared bit-exactness harness sweeps, so a family registered in
    _FAMILIES but absent there would pass lint unvalidated AND silently
    lose its cross-backend coverage — exactly the half-registered state
    this check exists to catch."""
    covered = {p.family for p in test_instances()}
    missing = set(_FAMILIES) - covered
    if missing:
        raise AssertionError(
            f"registered famil{'ies' if len(missing) > 1 else 'y'} "
            f"{sorted(missing)} missing from test_instances() — add a "
            "canonical instance so lint and the shared harness cover it")
    for prog in test_instances():
        validate_program(prog)
    return registered_families()
