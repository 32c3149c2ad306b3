"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler in libtpu compiles for a chip that is
described, not attached. This catches what interpret mode cannot — shapes
Mosaic refuses, VMEM overruns at the autotuned blocks, programs that do
not fit the 16 GiB of HBM. It says nothing about results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load libtpu, and every test worker imports this
file. The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import program as program_mod
from repro.kernels import ops as kernel_ops
from repro.kernels.frugal_update import frugal_program_pallas_dma
from repro.roofline.analysis import hw_for
from repro.roofline.autotune import autotune_blocks

HBM_BYTES = 16 * 2 ** 30
DENSE_G, DENSE_T = 2 ** 22, 64
SPARSE_L, SPARSE_K = 524_288 * 3, 4096      # the SLO fleet's ~1.57M lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _device_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


FAMILIES = program_mod.registered_families()


@pytest.mark.parametrize("family", FAMILIES)
def test_dense_dma_kernel_compiles_at_tuned_blocks(one_chip, family):
    """The DMA kernel at G=2^22, T=64 with the blocks the autotuner picks
    for tpu-v5e: Mosaic accepts the body and the VMEM it asks for."""
    prog = program_mod.make_program(family)
    base = program_mod.family_base(prog.kernel_family)
    layout = base.layout
    bg, bt = autotune_blocks(prog, DENSE_G, DENSE_T, hw=hw_for("tpu-v5e"))

    def dispatch(items, words, quantile, seed, scalars):
        return frugal_program_pallas_dma(base, items, words, quantile, seed,
                                         scalars, block_g=bg, block_t=bt)

    compiled = jax.jit(dispatch).lower(
        _shape(one_chip, (DENSE_T, DENSE_G), jnp.float32),
        tuple(_shape(one_chip, (DENSE_G,), dt) for dt in layout.word_dtypes),
        _shape(one_chip, (DENSE_G,), jnp.float32),
        _shape(one_chip, (), jnp.int32),
        tuple(_shape(one_chip, (), jnp.int32)
              for _ in layout.scalar_names)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("family", FAMILIES)
def test_donated_sparse_round_compiles_in_place(one_chip, family):
    """The donated XLA event round at L≈1.57M lanes, K=4096 events: no
    kernel, a scatter per state plane, and no temporary the size of a
    plane (the scatters update the donated buffers in place)."""
    base = program_mod.family_base(
        program_mod.make_program(family).kernel_family)
    layout = base.layout
    lanes = _shape(one_chip, (SPARSE_K,), jnp.int32)
    compiled = kernel_ops._sparse_scatter_donated.lower(
        lanes, _shape(one_chip, (SPARSE_K,), jnp.float32), lanes,
        tuple(_shape(one_chip, (SPARSE_L,), jnp.float32)
              for _ in layout.plane_fields),
        _shape(one_chip, (SPARSE_L,), jnp.int32), lanes,
        _shape(one_chip, (SPARSE_L,), jnp.float32),
        _shape(one_chip, (), jnp.int32), _shape(one_chip, (), jnp.int32),
        tuple(_shape(one_chip, (), jnp.int32) for _ in layout.scalar_names),
        program=base).compile()
    text = compiled.as_text()
    assert text.count(" scatter(") >= layout.num_planes + 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * SPARSE_L
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_ingest_compiles_for_four_chips(topo, monkeypatch):
    """The lane-sharded ingest of a 2^24-group, two-quantile 2U fleet on a
    v5e 2x2: each chip takes its own [64, 2^22] column slab and fans it
    out to its 2^23 lanes through the DMA kernel, with no collective, in
    one chip's memory. The dispatch asks the host for its platform, so the
    test names the chip it compiles for."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.parallel import group_sharding

    monkeypatch.setattr(kernel_ops, "detect_platform", lambda: "tpu")
    monkeypatch.setattr(kernel_ops, "supports_compiled_kernels",
                        lambda: True)
    monkeypatch.setattr(kernel_ops, "_tuned_blocks",
                        lambda prog, g, t: autotune_blocks(
                            prog, g, t, hw=hw_for("tpu-v5e")))
    groups, q, shards = 4 * DENSE_G, 2, 4
    mesh = Mesh(topo.devices[:shards], (group_sharding.GROUP_AXIS,))
    prog = program_mod.make_program("2u")
    fn = group_sharding._sharded_ingest_fn(
        mesh, group_sharding.GROUP_AXIS, prog, groups * q // shards,
        DENSE_T, q)
    items = jax.ShapeDtypeStruct(
        (DENSE_T, groups), jnp.float32,
        sharding=NamedSharding(mesh, P(None, group_sharding.GROUP_AXIS)))
    lane = jax.ShapeDtypeStruct(
        (groups * q,), jnp.float32,
        sharding=NamedSharding(mesh, P(group_sharding.GROUP_AXIS)))
    scalar = jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    lowered = fn.lower(items, lane, scalar, scalar, scalar,
                       *(lane,) * prog.layout.num_planes)
    assert lowered.as_text().startswith("module @jit_sharded_ingest")
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not any(op in text for op in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute"))
    assert _device_bytes(compiled) < HBM_BYTES


def test_row_stack_compiles_for_four_chips(topo):
    """The lane-sharded staging's device half at the four-chip cell's size:
    64 rows of 2^24 columns, each cut four ways, stack into the [64, 2^24]
    chunk cut the same way. Each chip stacks its own [64, 2^22] slab, with
    no collective, in one chip's memory."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.parallel import group_sharding

    axis = group_sharding.GROUP_AXIS
    mesh = Mesh(topo.devices[:4], (axis,))
    row = jax.ShapeDtypeStruct((4 * DENSE_G,), jnp.float32,
                               sharding=NamedSharding(mesh, P(axis)))
    lowered = group_sharding._stack_rows_fn(mesh, axis).lower(
        *(row,) * DENSE_T)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert not any(op in text for op in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute"))
    assert _device_bytes(compiled) < 3 * DENSE_T * DENSE_G * 4
