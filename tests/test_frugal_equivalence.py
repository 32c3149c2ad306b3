"""Vectorized JAX Frugal-1U/2U must agree bit-exactly with the paper's
scalar pseudocode when fed the same uniforms (per-group independence), and
the whole FUSED stack (core scan / jnp ref / Pallas kernel, shared counter
RNG, packed 2U state) must agree bit-exactly layer-to-layer under one key."""
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

from repro.core import (
    frugal1u_init,
    frugal1u_process,
    frugal2u_init,
    frugal2u_process,
    pack_step_sign,
    unpack_step_sign,
)
from repro.core import rng as crng
from repro.core.reference import frugal1u_scalar, frugal2u_scalar
from repro.core import program as program_mod
from repro.kernels import frugal_update_blocked
from repro.kernels import ref as kref

_P1U = program_mod.family_base("1u")
_P2U = program_mod.family_base("2u")


def _run_both_1u(stream, rands, q):
    ref = frugal1u_scalar(list(stream), list(rands), quantile=q)
    stt = frugal1u_init(1)
    stt, _ = frugal1u_process(
        stt, jnp.asarray(stream, jnp.float32)[:, None],
        rand=jnp.asarray(rands, jnp.float32)[:, None], quantile=q,
    )
    return ref, float(stt.m[0])


def _run_both_2u(stream, rands, q):
    ref = frugal2u_scalar(list(stream), list(rands), quantile=q)
    stt = frugal2u_init(1)
    stt, _ = frugal2u_process(
        stt, jnp.asarray(stream, jnp.float32)[:, None],
        rand=jnp.asarray(rands, jnp.float32)[:, None], quantile=q,
    )
    return ref, float(stt.m[0])


@pytest.mark.parametrize("q", [0.1, 0.25, 0.5, 0.75, 0.9])
@pytest.mark.parametrize("algo", ["1u", "2u"])
def test_jax_matches_scalar_random_integer_streams(q, algo, rng):
    n = 500
    stream = rng.integers(0, 100, size=n).astype(np.float64)
    rands = rng.random(n)
    run = _run_both_1u if algo == "1u" else _run_both_2u
    ref, got = run(stream, rands, q)
    assert got == pytest.approx(ref, abs=1e-4), f"{algo} diverged from paper pseudocode"


@pytest.mark.parametrize("algo", ["1u", "2u"])
def test_groups_are_independent(algo, rng):
    """Each group's trajectory must equal a solo run of that group."""
    n, G = 200, 8
    streams = rng.integers(0, 50, size=(n, G)).astype(np.float64)
    rands = rng.random((n, G))
    if algo == "1u":
        st = frugal1u_init(G)
        st, _ = frugal1u_process(st, jnp.asarray(streams, jnp.float32),
                                 rand=jnp.asarray(rands, jnp.float32), quantile=0.5)
        for g in range(G):
            ref = frugal1u_scalar(list(streams[:, g]), list(rands[:, g]), quantile=0.5)
            assert float(st.m[g]) == pytest.approx(ref, abs=1e-4)
    else:
        st = frugal2u_init(G)
        st, _ = frugal2u_process(st, jnp.asarray(streams, jnp.float32),
                                 rand=jnp.asarray(rands, jnp.float32), quantile=0.5)
        for g in range(G):
            ref = frugal2u_scalar(list(streams[:, g]), list(rands[:, g]), quantile=0.5)
            assert float(st.m[g]) == pytest.approx(ref, abs=1e-4)


# ------------------------------------------------- fused-stack equivalence
def _mk_items(t, g, seed=0, domain=200):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, domain, (t, g)), jnp.float32)


@pytest.mark.parametrize("t,g", [(1, 1), (7, 3), (300, 130), (512, 256)])
@pytest.mark.parametrize("q", [0.1, 0.5, 0.9])
def test_fused_1u_kernel_matches_fused_ref_bit_exact(t, g, q):
    """Fused Pallas kernel and fused jnp ref share the counter scheme —
    agreement must be bit-exact, with NO uniforms tensor anywhere."""
    items = _mk_items(t, g, seed=t * 131 + g)
    m = jnp.zeros((g,), jnp.float32)
    qv = jnp.full((g,), q, jnp.float32)
    seed = 77
    (got,) = frugal_update_blocked(items, (m,), qv, seed, program=_P1U,
                                   block_g=128, block_t=64, interpret=True)
    want = kref.frugal1u_ref_fused(items, m, qv, seed)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("t,g", [(1, 1), (7, 3), (300, 130), (512, 256)])
@pytest.mark.parametrize("q", [0.1, 0.5, 0.9])
def test_fused_2u_kernel_matches_fused_ref_bit_exact(t, g, q):
    """2U adds the packed (step, sign) word — round-trip must not cost a bit."""
    items = _mk_items(t, g, seed=t * 17 + g)
    m = jnp.zeros((g,), jnp.float32)
    step = jnp.ones((g,), jnp.float32)
    sign = jnp.ones((g,), jnp.float32)
    qv = jnp.full((g,), q, jnp.float32)
    seed = 99
    got = frugal_update_blocked(items, (m, step, sign), qv, seed,
                                program=_P2U, block_g=128, block_t=64,
                                interpret=True)
    want = kref.frugal2u_ref_fused(items, m, step, sign, qv, seed)
    for a, b, name in zip(got, want, ("m", "step", "sign")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} mismatch ({t},{g},q={q})")


def test_fused_full_stack_bit_exact_under_one_key():
    """core process(key) == kernels.ref fused == fused Pallas kernel: one key
    discipline, three implementations, zero tolerance."""
    t, g = 257, 67
    items = _mk_items(t, g, seed=5)
    key = jax.random.PRNGKey(123)
    seed = crng.seed_from_key(key)

    st2 = frugal2u_init(g)
    core_out, _ = frugal2u_process(st2, items, key=key, quantile=0.7)
    qv = jnp.full((g,), 0.7, jnp.float32)
    ref_out = kref.frugal2u_ref_fused(items, st2.m, st2.step, st2.sign, qv, seed)
    kern_out = frugal_update_blocked(items, (st2.m, st2.step, st2.sign), qv,
                                     seed, program=_P2U, interpret=True)
    for a, b, c in zip(core_out, ref_out, kern_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(b), np.asarray(c))


def test_fused_deterministic_given_key_and_sensitive_to_it():
    t, g = 400, 32
    items = _mk_items(t, g, seed=9, domain=1000)
    st1 = frugal2u_init(g)
    a, _ = frugal2u_process(st1, items, key=jax.random.PRNGKey(0))
    b, _ = frugal2u_process(st1, items, key=jax.random.PRNGKey(0))
    c, _ = frugal2u_process(st1, items, key=jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(a.m), np.asarray(b.m))
    assert not np.array_equal(np.asarray(a.m), np.asarray(c.m)), \
        "different keys must give different trajectories"


def test_fused_t_offset_continuation_matches_one_shot():
    """Splitting a stream at any point and carrying t_offset must reproduce
    the unsplit trajectory bit-for-bit (the chunked-ingest contract)."""
    t, g = 300, 19
    items = _mk_items(t, g, seed=4)
    qv = jnp.full((g,), 0.5, jnp.float32)
    m0 = jnp.zeros((g,), jnp.float32)
    seed = 31337
    whole = kref.frugal1u_ref_fused(items, m0, qv, seed)
    for cut in (1, 100, 237, 299):
        first = kref.frugal1u_ref_fused(items[:cut], m0, qv, seed)
        both = kref.frugal1u_ref_fused(items[cut:], first, qv, seed, t_offset=cut)
        np.testing.assert_array_equal(np.asarray(both), np.asarray(whole),
                                      err_msg=f"cut at {cut}")


def test_pack_step_sign_roundtrip_exact():
    """(step, sign) -> one int32 word -> (step, sign), bit-exact over the
    contractual domain: |step| in {0} ∪ [2^-63, 2^32), sign ∈ {±1}."""
    rng = np.random.default_rng(12)
    mag = np.concatenate([
        np.exp2(rng.uniform(-63.0, 0.0, 3000)).astype(np.float32),
        rng.uniform(1.0, 2.0 ** 32 - 2 ** 9, 3000).astype(np.float32),
        np.zeros(10, np.float32),
        np.asarray([1.0, 2.0, 0.5, 3.75, 2.0 ** 31, 2.0 ** -63], np.float32),
    ])
    step = jnp.asarray(mag * rng.choice([-1.0, 1.0], mag.shape).astype(np.float32))
    sign = jnp.asarray(rng.choice([-1.0, 1.0], mag.shape), jnp.float32)
    packed = pack_step_sign(step, sign)
    assert packed.dtype == jnp.int32
    step2, sign2 = unpack_step_sign(packed)
    np.testing.assert_array_equal(np.asarray(step2), np.asarray(step))
    np.testing.assert_array_equal(np.asarray(sign2), np.asarray(sign))


def test_pack_step_sign_saturates_out_of_domain_magnitudes():
    """|step| >= 2^32 must saturate (direction preserved), never corrupt."""
    step = jnp.asarray([2.0 ** 33, -(2.0 ** 40), 1e38], jnp.float32)
    sign = jnp.asarray([-1.0, 1.0, -1.0], jnp.float32)
    step2, sign2 = unpack_step_sign(pack_step_sign(step, sign))
    np.testing.assert_array_equal(np.asarray(sign2), np.asarray(sign))
    max_step = np.float32(2.0 ** 32 * (1.0 - 2.0 ** -24))
    np.testing.assert_array_equal(
        np.asarray(step2), np.asarray([max_step, -max_step, max_step]))


def test_counter_uniform_statistics():
    """The on-chip counter hash must look uniform: mean/variance/range and
    lag-1 correlation across ticks within loose 4-sigma bands."""
    u = np.asarray(crng.counter_uniform(
        42, jnp.arange(20_000)[:, None], jnp.arange(8)[None, :])).ravel()
    n = u.size
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 4 * (1 / np.sqrt(12 * n))
    assert abs(u.var() - 1 / 12) < 0.002
    lag1 = np.corrcoef(u[:-1], u[1:])[0, 1]
    assert abs(lag1) < 4 / np.sqrt(n)


_INT32_MIN, _INT32_MAX = -2 ** 31, 2 ** 31 - 1
_N_LANES = 1024


@pytest.mark.parametrize("g_offset", [0, _INT32_MAX - _N_LANES // 2],
                         ids=["lanes_from_0", "lanes_past_int32"])
@pytest.mark.parametrize("t", [
    0, _INT32_MAX, crng.wrap_i32(2 ** 31 + 12_345),
    crng.wrap_i32(2 ** 31 - 7) + np.arange(_N_LANES, dtype=np.int64)],
    ids=["t0", "t_int32_max", "t_wrapped", "t_per_lane"])
@pytest.mark.parametrize("seed", [
    0, 1, -1, _INT32_MIN, _INT32_MAX,
    int(np.random.default_rng(2014).integers(_INT32_MIN, _INT32_MAX))])
def test_counter_uniform_host_bit_equal_to_device(seed, t, g_offset):
    """The host twin hashes exactly the device's words: same uint32 wrap
    for seeds, ticks and lanes outside int32, same fmix32 rounds, same
    mantissa fill."""
    lanes = g_offset + np.arange(_N_LANES, dtype=np.int64)
    dev = np.asarray(crng.counter_uniform(
        seed, jnp.asarray(np.asarray(t).astype(np.int32)),
        jnp.asarray(lanes.astype(np.int32))))
    host = crng.counter_uniform_host(seed, t, lanes)
    assert isinstance(host, np.ndarray) and host.dtype == np.float32
    assert host.shape == dev.shape == (_N_LANES,)
    np.testing.assert_array_equal(host.view(np.int32), dev.view(np.int32))


# --------------------------------------------------------- property testing
if HAS_HYPOTHESIS:
    stream_strat = st.lists(
        st.integers(min_value=0, max_value=1000), min_size=1, max_size=120
    )
    rand_strat = st.randoms(use_true_random=False)

    @settings(max_examples=60, deadline=None)
    @given(stream=stream_strat, seed=st.integers(0, 2**31 - 1),
           q=st.sampled_from([0.1, 0.5, 0.9]))
    def test_property_1u_equivalence(stream, seed, q):
        r = np.random.default_rng(seed).random(len(stream))
        ref, got = _run_both_1u(np.asarray(stream, np.float64), r, q)
        assert got == pytest.approx(ref, abs=1e-4)

    @settings(max_examples=60, deadline=None)
    @given(stream=stream_strat, seed=st.integers(0, 2**31 - 1),
           q=st.sampled_from([0.1, 0.5, 0.9]))
    def test_property_2u_equivalence(stream, seed, q):
        r = np.random.default_rng(seed).random(len(stream))
        ref, got = _run_both_2u(np.asarray(stream, np.float64), r, q)
        assert got == pytest.approx(ref, abs=1e-4)

    @settings(max_examples=60, deadline=None)
    @given(stream=stream_strat, seed=st.integers(0, 2**31 - 1))
    def test_property_1u_moves_at_most_one(stream, seed):
        """Invariant: Frugal-1U moves by exactly 0 or ±1 per item."""
        r = np.random.default_rng(seed).random(len(stream))
        trace = []
        frugal1u_scalar(np.asarray(stream, np.float64), r, quantile=0.5, trace=trace)
        prev = 0.0
        for m in trace:
            assert abs(m - prev) <= 1.0 + 1e-9
            prev = m

    @settings(max_examples=60, deadline=None)
    @given(stream=stream_strat, seed=st.integers(0, 2**31 - 1))
    def test_property_2u_never_moves_past_trigger_item(stream, seed):
        """Invariant (paper lines 7-10/18-21): an update clamps at the item."""
        r = np.random.default_rng(seed).random(len(stream))
        trace = []
        frugal2u_scalar(np.asarray(stream, np.float64), r, quantile=0.5, trace=trace)
        prev = 0.0
        for s_i, m in zip(stream, trace):
            lo, hi = min(prev, s_i), max(prev, s_i)
            assert lo - 1e-9 <= m <= hi + 1e-9, "2U estimate escaped [prev, item] hull"
            prev = m

    @settings(max_examples=40, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_property_pack_roundtrip(seed):
        rng2 = np.random.default_rng(seed)
        mag = np.float32(rng2.uniform(0.5, 1.5) * 2.0 ** rng2.integers(-62, 31))
        step = jnp.float32(mag * rng2.choice([-1.0, 1.0]))
        sign = jnp.float32(rng2.choice([-1.0, 1.0]))
        step2, sign2 = unpack_step_sign(pack_step_sign(step, sign))
        assert float(step2) == float(step) and float(sign2) == float(sign)

else:

    def test_property_tests_need_hypothesis():
        pytest.skip("hypothesis not installed — property tests not collected "
                    "(pip install -r requirements-dev.txt)")
