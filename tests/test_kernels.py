"""Program-kernel validation: the ONE Pallas kernel family
(kernels.frugal_update via kernels.ops.frugal_update_blocked) must match
the independent jnp oracles (kernels/ref.py) and the program-generic scan
bit-for-bit, for every registered program, across shapes and block tilings
(interpret mode executes the kernel body on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

# Only the property tests need hypothesis; a missing dev dep must not kill
# collection of the whole suite under `pytest -x` (see requirements-dev.txt).
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:
    HAS_HYPOTHESIS = False

from repro.core import program as program_mod
from repro.core.frugal import program_process_seeded
from repro.kernels import frugal_update_blocked
from repro.kernels import ref

pytestmark = pytest.mark.kernel

SEED = 2024


def _mk(t, g, seed=0, dtype=np.float32, domain=200):
    rng = np.random.default_rng(seed)
    items = rng.integers(0, domain, size=(t, g)).astype(dtype)
    m = rng.integers(0, domain, size=g).astype(np.float32)
    return jnp.asarray(items), jnp.asarray(m)


def _init_planes(program, m):
    """Program planes from an m vector: heads start at m (copies), pair
    planes at 1 — the same convention GroupedQuantileSketch.create uses."""
    layout = program.layout
    return tuple(
        m if f == "m" else (jnp.array(m) if f in layout.heads
                            else jnp.ones_like(m))
        for f in layout.plane_fields)


SHAPES = [
    (1, 1), (7, 3), (64, 128), (256, 128), (300, 130),  # non-multiples too
    (512, 256), (1024, 64), (33, 257),
]


@pytest.mark.parametrize("t,g", SHAPES)
@pytest.mark.parametrize("q", [0.1, 0.5, 0.9])
def test_program_kernel_1u_matches_independent_ref(t, g, q):
    items, m = _mk(t, g, seed=t * 1000 + g)
    qv = jnp.full((g,), q, jnp.float32)
    prog = program_mod.family_base("1u")
    (got,) = frugal_update_blocked(items, (m,), qv, SEED, program=prog,
                                   interpret=True)
    want = ref.frugal1u_ref_fused(items, m, qv, SEED)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t,g", SHAPES)
@pytest.mark.parametrize("q", [0.1, 0.5, 0.9])
def test_program_kernel_2u_matches_independent_ref(t, g, q):
    items, m = _mk(t, g, seed=t * 7 + g)
    step = jnp.ones((g,), jnp.float32)
    sign = jnp.ones((g,), jnp.float32)
    qv = jnp.full((g,), q, jnp.float32)
    prog = program_mod.family_base("2u")
    got = frugal_update_blocked(items, (m, step, sign), qv, SEED,
                                program=prog, interpret=True)
    want = ref.frugal2u_ref_fused(items, m, step, sign, qv, SEED)
    for a, b, name in zip(got, want, ("m", "step", "sign")):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{name} mismatch at ({t},{g},q={q})")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtype_sweep(dtype):
    """Items may arrive bf16 (activations); state math runs in f32."""
    t, g = 128, 128
    rng = np.random.default_rng(3)
    items = jnp.asarray(rng.integers(0, 50, (t, g)), dtype)
    m = jnp.zeros((g,), jnp.float32)
    qv = jnp.full((g,), 0.5, jnp.float32)
    prog = program_mod.family_base("1u")
    (got,) = frugal_update_blocked(items, (m,), qv, SEED, program=prog,
                                   interpret=True)
    want = ref.frugal1u_ref_fused(items.astype(jnp.float32), m, qv, SEED)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _scan_planes(program, items, planes, qv, seed):
    out, _ = program_process_seeded(program, planes, items, seed, qv)
    return tuple(np.asarray(p) for p in out)


def test_program_kernel_block_shape_sweep_every_family():
    """Block shapes must not change a single bit of any program's result
    (absolute-index RNG keys + VMEM-resident plane state). One loop over
    the registry is the whole tiling matrix — the five per-rule sweeps this
    replaces are a registry entry each."""
    t, g = 160, 130
    items, m = _mk(t, g, seed=11)
    qv = jnp.full((g,), 0.7, jnp.float32)
    for prog in program_mod.test_instances():
        planes = _init_planes(prog, jnp.zeros((g,), jnp.float32))
        want = _scan_planes(prog, items, planes, qv, SEED)
        for bg in (64, 128):
            for bt in (32, 256):
                got = frugal_update_blocked(items, planes, qv, SEED,
                                            program=prog, block_g=bg,
                                            block_t=bt, interpret=True)
                for f, a, b in zip(prog.layout.plane_fields, got, want):
                    np.testing.assert_array_equal(
                        np.asarray(a), b,
                        err_msg=f"{prog.family} {f} block ({bt},{bg})")


def test_kernel_nan_padding_is_noop():
    """NaN ticks must leave state untouched (the ragged/padding contract),
    for every registered program — including the window rules, whose epoch
    restarts are gated on item validity."""
    t, g = 64, 128
    items, m = _mk(t, g, seed=5)
    qv = jnp.full((g,), 0.5, jnp.float32)
    items2 = jnp.concatenate([items, jnp.full((32, g), jnp.nan, jnp.float32)])
    for prog in program_mod.test_instances():
        planes = _init_planes(prog, m)
        out1 = frugal_update_blocked(items, planes, qv, SEED, program=prog,
                                     interpret=True)
        out2 = frugal_update_blocked(items2, planes, qv, SEED, program=prog,
                                     interpret=True)
        for f, a, b in zip(prog.layout.plane_fields, out1, out2):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{prog.family} {f} perturbed by NaN ticks")


@pytest.mark.parametrize("family",
                         [p.family for p in program_mod.test_instances()])
@pytest.mark.parametrize("donate", [False, True])
def test_sparse_round_matches_dense_lane_tick(family, donate):
    """The O(events) sparse round (gather→tick→scatter, functional and
    donated) must replay the dense per-lane-clock round over all L lanes
    bit-for-bit: non-zero g_offset, several rounds, and mask-0 NaN pad
    slots on event-free lanes."""
    from repro.api.fleet import _lane_tick
    from repro.kernels import ops as kernel_ops

    prog = next(p for p in program_mod.test_instances()
                if p.family == family)
    base = program_mod.family_base(prog.kernel_family)
    scalars = tuple(jnp.asarray(v, jnp.int32) for v in prog.scalar_values())
    L, g_off = 96, 1000
    rng = np.random.default_rng(31)
    m0 = jnp.asarray(rng.integers(0, 200, L), jnp.float32)
    qv = jnp.asarray(rng.choice([0.1, 0.5, 0.9], L), jnp.float32)
    planes_d = _init_planes(prog, m0)
    planes_s = tuple(jnp.array(p) for p in planes_d)
    ticks_d = jnp.zeros((L,), jnp.int32)
    ticks_s = jnp.zeros((L,), jnp.int32)
    for r, k in enumerate((1, 40, 96, 70)):
        lanes = np.sort(rng.choice(L, k, replace=False)).astype(np.int32)
        vals = rng.integers(0, 200, k).astype(np.float32)
        items = np.full(L, np.nan, np.float32)
        items[lanes] = vals
        mask = np.ones(k, np.int32)
        if k < L:   # explicit mask-0 pad on an event-free lane
            pad = next(i for i in range(L) if i not in set(lanes.tolist()))
            lanes = np.append(lanes, np.int32(pad))
            vals = np.append(vals, np.float32(np.nan))
            mask = np.append(mask, np.int32(0))
        planes_d = _lane_tick(planes_d, ticks_d, qv, jnp.asarray(items),
                              jnp.int32(SEED), g_off, scalars, program=base)
        ticks_d = ticks_d + jnp.asarray(~np.isnan(items), jnp.int32)
        planes_s, ticks_s = kernel_ops.frugal_update_sparse(
            lanes, vals, mask, planes_s, ticks_s, qv, SEED, program=prog,
            g_offset=g_off, donate=donate)
        for f, a, b in zip(prog.layout.plane_fields, planes_d, planes_s):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{family} plane {f!r} diverges from the dense "
                        f"round at round {r}")
        np.testing.assert_array_equal(
            np.asarray(ticks_d), np.asarray(ticks_s),
            err_msg=f"{family} lane clocks diverge at round {r}")


def test_kernel_per_lane_quantiles():
    """One call, heterogeneous quantile targets across lanes."""
    t, g = 2048, 8
    rng = np.random.default_rng(9)
    items = jnp.asarray(rng.integers(0, 1000, (t, g)), jnp.float32)
    m = jnp.full((g,), 500.0, jnp.float32)
    qv = jnp.asarray([0.1, 0.2, 0.3, 0.4, 0.6, 0.7, 0.8, 0.9], jnp.float32)
    step = jnp.ones((g,), jnp.float32)
    sign = jnp.ones((g,), jnp.float32)
    prog = program_mod.family_base("2u")
    m2, _, _ = frugal_update_blocked(items, (m, step, sign), qv, SEED,
                                     program=prog, interpret=True)
    # final estimates must be ordered like their target quantiles (loose check)
    est = np.asarray(m2)
    assert est[0] < est[-1], f"q10 {est[0]} !< q90 {est[-1]}"
    want = ref.frugal2u_ref_fused(items, m, step, sign, qv, SEED)
    np.testing.assert_array_equal(est, np.asarray(want[0]))


def test_rule_scalars_are_dynamic_operands():
    """Two instances of one family with different parameters must share the
    compiled kernel (family_base compile key) yet produce their own
    trajectories — the scalar slots are dynamic operands."""
    t, g = 300, 7
    items, _ = _mk(t, g, seed=8, domain=500)
    qv = jnp.full((g,), 0.3, jnp.float32)
    m0 = jnp.zeros((g,), jnp.float32)
    one = jnp.ones((g,), jnp.float32)
    outs = {}
    for hl in (8, 48):
        prog = program_mod.make_program("2u-decay", half_life=hl)
        got = frugal_update_blocked(items, (m0, one, one), qv, SEED,
                                    program=prog, block_g=4, block_t=64,
                                    interpret=True)
        want = _scan_planes(prog, items, (m0, one, one), qv, SEED)
        for f, a, b in zip(prog.layout.plane_fields, got, want):
            np.testing.assert_array_equal(np.asarray(a), b,
                                          err_msg=f"half_life={hl} {f}")
        outs[hl] = np.asarray(got[1])
    assert not np.array_equal(outs[8], outs[48]), \
        "different half-lives must yield different step trajectories"


if HAS_HYPOTHESIS:

    @settings(max_examples=15, deadline=None)
    @given(
        t=st.integers(1, 80),
        g=st.integers(1, 140),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_property_program_kernel_equals_ref_arbitrary_shapes(t, g, seed):
        items, m = _mk(t, g, seed=seed)
        qv = jnp.full((g,), 0.5, jnp.float32)
        step = jnp.ones((g,), jnp.float32)
        sign = jnp.ones((g,), jnp.float32)
        prog = program_mod.family_base("2u")
        got = frugal_update_blocked(items, (m, step, sign), qv, seed,
                                    program=prog, block_g=128, block_t=64,
                                    interpret=True)
        want = ref.frugal2u_ref_fused(items, m, step, sign, qv, seed)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @settings(max_examples=10, deadline=None)
    @given(
        t=st.integers(1, 60),
        g=st.integers(1, 100),
        seed=st.integers(0, 2**31 - 1),
        family=st.sampled_from([p.family
                                for p in program_mod.test_instances()]),
    )
    def test_property_program_kernel_equals_scan_arbitrary_shapes(
            t, g, seed, family):
        prog = next(p for p in program_mod.test_instances()
                    if p.family == family)
        items, m = _mk(t, g, seed=seed)
        qv = jnp.full((g,), 0.5, jnp.float32)
        planes = _init_planes(prog, m)
        got = frugal_update_blocked(items, planes, qv, seed, program=prog,
                                    block_g=128, block_t=64, interpret=True)
        want = _scan_planes(prog, items, planes, qv, seed)
        for f, a, b in zip(prog.layout.plane_fields, got, want):
            np.testing.assert_array_equal(np.asarray(a), b,
                                          err_msg=f"{family} {f}")

else:

    def test_property_tests_need_hypothesis():
        pytest.skip("hypothesis not installed — property tests not collected "
                    "(pip install -r requirements-dev.txt)")
