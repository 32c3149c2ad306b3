"""Counter-based on-chip RNG shared by every layer of the frugal hot path.

The frugal update consumes one uniform per (tick, group). Materializing those
as a ``rand[T, G]`` HBM operand doubles the kernel's input bandwidth — the
items array is [T, G] and so is the uniforms array — which is exactly the
waste that makes bandwidth-bound sketch ingestion run at half speed (see
DESIGN.md §4). Instead, every consumer derives the uniform *in registers*
from a stateless counter hash:

    u(seed, t, g) = bits_to_unit_f32(mix(mix(seed + t*C1) + g*C2))

keyed on the *absolute* tick index ``t`` (block-local index + block offset +
stream offset) and the *absolute* group index ``g``. Because the key is
absolute, the generated stream is invariant to kernel block shape AND to how a
long stream is chunked — `frugal*_pallas_fused`, `kernels.ref.*_ref_fused`,
`core.frugal.frugal*_process(key=...)` and `core.streaming.ingest_stream` all
produce bit-identical trajectories from the same key (property-tested in
tests/test_frugal_equivalence.py / tests/test_streaming.py).

The mixer is two rounds of the murmur3 finalizer (fmix32) — a bijective
avalanche hash, far stronger than needed for the single ``r > q`` comparison
each uniform feeds. Everything is int32 arithmetic (2's-complement wraparound,
logical shifts) so the identical expression lowers both to XLA and to Mosaic
inside a Pallas TPU kernel body; no uint32 support is required.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

Array = jax.Array

# murmur3 fmix32 multipliers / combine constants, as int32 bit patterns.
_M1 = np.int32(np.uint32(0x85EBCA6B).view(np.int32))
_M2 = np.int32(np.uint32(0xC2B2AE35).view(np.int32))
_C_TICK = np.int32(np.uint32(0x9E3779B9).view(np.int32))   # golden ratio
_C_GROUP = np.int32(np.uint32(0x85EBCA77).view(np.int32))
_EXP_ONE = np.int32(0x3F800000)                            # f32 bits of 1.0
# The same constants as uint32, for the host twin.
_M1_U, _M2_U, _C_TICK_U, _C_GROUP_U, _EXP_ONE_U = (
    c.view(np.uint32) for c in (_M1, _M2, _C_TICK, _C_GROUP, _EXP_ONE))


def _fmix32(h: Array) -> Array:
    """murmur3 finalizer: bijective full-avalanche mix of an int32 word."""
    h = h ^ jax.lax.shift_right_logical(h, 16)
    h = h * _M1
    h = h ^ jax.lax.shift_right_logical(h, 13)
    h = h * _M2
    h = h ^ jax.lax.shift_right_logical(h, 16)
    return h


def counter_bits(seed, t, g) -> Array:
    """Raw hash word for stream position (t, g) under `seed`. int32, broadcasts."""
    seed = jnp.asarray(seed, jnp.int32)
    t = jnp.asarray(t, jnp.int32)
    g = jnp.asarray(g, jnp.int32)
    h = _fmix32(seed + t * _C_TICK)
    return _fmix32(h + g * _C_GROUP)


def counter_uniform(seed, t, g) -> Array:
    """Uniform in [0, 1) for stream position (t, g): mantissa-fill trick.

    Top 23 hash bits become the mantissa of a float in [1, 2); subtracting 1
    yields an exact dyadic uniform in [0, 1) with no divisions.
    """
    bits = counter_bits(seed, t, g)
    mant = jax.lax.shift_right_logical(bits, 9) | _EXP_ONE
    return jax.lax.bitcast_convert_type(mant, jnp.float32) - 1.0


def _u32_host(x) -> np.ndarray:
    """Integer (array) folded to uint32 two's complement: `wrap_i32`'s
    wrap, so a seed, tick or lane past int32 hashes as the device's does."""
    if isinstance(x, (int, np.integer)):
        return np.uint32(int(x) & 0xFFFFFFFF)
    return np.asarray(x).astype(np.uint32, copy=False)   # C cast: mod 2^32


def _fmix32_host(h: np.ndarray) -> np.ndarray:
    """`_fmix32` in numpy uint32: wrapping multiplies, logical shifts."""
    h = h ^ (h >> np.uint32(16))
    h = h * _M1_U
    h = h ^ (h >> np.uint32(13))
    h = h * _M2_U
    return h ^ (h >> np.uint32(16))


def counter_uniform_host(seed, t, g) -> np.ndarray:
    """`counter_uniform` on the host, in numpy: bit-identical float32
    uniforms with no JAX call, for reads that already hold host data."""
    with np.errstate(over="ignore"):    # uint32 scalar multiplies wrap
        h = _fmix32_host(_u32_host(seed) + _u32_host(t) * _C_TICK_U)
        h = _fmix32_host(h + _u32_host(g) * _C_GROUP_U)
    mant = (h >> np.uint32(9)) | _EXP_ONE_U
    return np.asarray(mant).view(np.float32) - np.float32(1.0)


def wrap_i32(n: int) -> int:
    """Fold an unbounded Python tick counter into int32 two's-complement.

    The counter hash runs on int32, whose adds wrap mod 2^32 — applying the
    SAME wrap host-side keeps `jnp.asarray(t, int32)` from overflowing on
    streams past 2^31 ticks while preserving chunk invariance exactly (the
    wrapped offset plus the in-kernel int32 tick index wraps identically for
    every chunking). The uniform stream itself has period 2^32 ticks.
    """
    n = n & 0xFFFFFFFF
    return n - 0x100000000 if n >= 0x80000000 else n


def seed_from_key(key: Array) -> Array:
    """Fold a JAX PRNG key (typed or raw uint32 vector) into one int32 seed."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        data = jax.random.key_data(key)
    else:
        data = key
    data = jax.lax.bitcast_convert_type(
        jnp.asarray(data, jnp.uint32).reshape(-1), jnp.int32)
    seed = data[0]
    for i in range(1, data.shape[0]):
        seed = _fmix32(seed * _C_TICK + data[i])
    return seed


def tick_uniforms(key: Array, num: int) -> Array:
    """[num] uniforms for ONE stream tick (monitor fleets: one item/group/step).

    Same counter discipline with t fixed at 0 — per-step freshness comes from
    splitting the key per step, as jax.random users already do.
    """
    return counter_uniform(seed_from_key(key), 0, jnp.arange(num, dtype=jnp.int32))
