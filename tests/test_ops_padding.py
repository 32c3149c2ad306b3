"""The ops.py padding contract, pinned for the program kernel pair:

  * T padding: NaN-padded ticks are bit-identical no-ops (NaN compares False
    both ways, so a padded tick never moves state);
  * G padding: lanes beyond the real lane count carry the layout's dummy
    state and are dropped on return — real lanes must be bit-identical to an
    unpadded call.

The kernel keys its on-chip RNG on absolute indices, so padding must not
perturb the uniforms real ticks consume — for ANY registered program.

Also pinned here: the interpret-dispatch seam. Explicit ``interpret=False``
off tpu/gpu must raise a ValueError naming ``frugal_update_auto`` (the old
seam forced the compiled Pallas path and crashed in the Mosaic lowering),
while ``interpret=None`` must pick a working lowering per platform.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import program as program_mod
from repro.kernels import (frugal_update_blocked, frugal_update_sparse,
                           frugal_update_auto)

SEED = 424242


def _mk(t, g, seed=0, domain=300):
    rng = np.random.default_rng(seed)
    items = jnp.asarray(rng.integers(0, domain, (t, g)), jnp.float32)
    m = jnp.asarray(rng.integers(0, domain, g), jnp.float32)
    return items, m


def _init_planes(program, m):
    layout = program.layout
    return tuple(
        m if f == "m" else (jnp.array(m) if f in layout.heads
                            else jnp.ones_like(m))
        for f in layout.plane_fields)


@pytest.fixture(params=[p.family for p in program_mod.test_instances()])
def program(request):
    return next(p for p in program_mod.test_instances()
                if p.family == request.param)


# ------------------------------------------------------------- NaN tick no-op
def test_nan_padded_ticks_are_bit_identical_noops(program):
    t, g = 96, 130
    items, m = _mk(t, g, seed=1)
    qv = jnp.full((g,), 0.5, jnp.float32)
    planes = _init_planes(program, m)
    nan_block = jnp.full((64, g), jnp.nan, jnp.float32)
    out1 = frugal_update_blocked(items, planes, qv, SEED, program=program,
                                 interpret=True)
    out2 = frugal_update_blocked(jnp.concatenate([items, nan_block]), planes,
                                 qv, SEED, program=program, interpret=True)
    for f, a, b in zip(program.layout.plane_fields, out1, out2):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{program.family}: {f} perturbed by NaN ticks")


# ------------------------------------------------------- G-lane padding drop
@pytest.mark.parametrize("g", [1, 127, 129, 250])
def test_padded_g_lanes_are_dropped(program, g):
    """A non-multiple-of-block G must return exactly [G] real lanes, each
    bit-identical to what a wider (pre-padded) call computes for them."""
    t = 64
    items, m = _mk(t, g, seed=g)
    qv = jnp.full((g,), 0.5, jnp.float32)
    planes = _init_planes(program, m)
    out = frugal_update_blocked(items, planes, qv, SEED, program=program,
                                interpret=True)
    assert all(x.shape == (g,) for x in out)

    # widen by hand with junk lanes; real lanes must be untouched
    gp = (-g) % 128
    items_w = jnp.pad(items, ((0, 0), (0, gp)), constant_values=123.0)
    q_w = jnp.pad(qv, (0, gp), constant_values=0.25)
    layout = program.layout
    planes_w = tuple(
        jnp.pad(p, (0, gp), constant_values=7.0 if f in layout.heads else 1.0)
        for f, p in zip(layout.plane_fields, planes))
    out_w = frugal_update_blocked(items_w, planes_w, q_w, SEED,
                                  program=program, interpret=True)
    for f, a, b in zip(layout.plane_fields, out, out_w):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)[:g],
            err_msg=f"{program.family}: {f} real lanes perturbed")


# ------------------------------------------------- interpret-dispatch seam
# These tests only make sense where no compiled lowering exists; the CI
# runners (CPU) are exactly that environment.
_cpu_only = pytest.mark.skipif(
    jnp.zeros(1).device.platform in ("tpu", "gpu"),
    reason="dispatch-refusal arms are for platforms without a compiled "
           "kernel lowering")


@_cpu_only
def test_explicit_compiled_request_off_accelerator_refuses(program):
    """interpret=False off tpu/gpu: a ValueError naming the auto entry
    point — never a Mosaic crash."""
    t, g = 8, 4
    items, m = _mk(t, g)
    planes = _init_planes(program, m)
    qv = jnp.full((g,), 0.5, jnp.float32)
    with pytest.raises(ValueError, match="frugal_update_auto"):
        frugal_update_blocked(items, planes, qv, SEED, program=program,
                              interpret=False)


@_cpu_only
def test_default_dispatch_runs_and_matches_interpret_kernel(program):
    """The default dispatch runs on CPU: the dense auto facade takes the
    jitted scan, bit-identical to the interpret-mode DMA kernel, and the
    sparse seam runs the jitted scatter pair."""
    g = 5
    _, m = _mk(1, g)
    planes = _init_planes(program, m)
    qv = jnp.full((g,), 0.5, jnp.float32)
    items, _ = _mk(16, g)
    out = frugal_update_auto(items, planes, qv, seed=SEED, program=program)
    out_k = frugal_update_blocked(items, planes, qv, SEED, program=program,
                                  interpret=True, kernel="dma")
    for f, a, b in zip(program.layout.plane_fields, out, out_k):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{program.family}: {f} diverges between the default "
                    "dispatch and the interpret DMA kernel")
    ticks = jnp.zeros((g,), jnp.int32)
    lanes = jnp.arange(4, dtype=jnp.int32)
    vals = jnp.asarray([5.0, 50.0, 500.0, 5000.0], jnp.float32)
    mask = jnp.ones((4,), jnp.int32)
    pl_sp, tk_sp = frugal_update_sparse(
        lanes, vals, mask, planes, ticks, qv, SEED, program=program)
    assert all(x.shape == (g,) for x in pl_sp)
    np.testing.assert_array_equal(np.asarray(tk_sp), [1, 1, 1, 1, 0])
