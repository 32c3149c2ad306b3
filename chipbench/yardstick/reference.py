"""Plain reference of the served semantics, in numpy alone.

Each lane runs Frugal-2U (arXiv:1407.1121, Algorithm 3, f(step) = 1) on its
own stream. The uniform that lane `lane` uses at its tick `t` is the
counter hash of the system's contract:

    h = fmix32(fmix32(seed + t * 0x9E3779B9) + lane * 0x85EBCA77)
    u = float32 with mantissa h >> 9 and exponent of 1.0, minus 1.0

(murmur3's fmix32, 32-bit wrapping arithmetic). A dense stream gives lane
g * Q + qi the item of group g at every absolute tick t. The DP release
adds Laplace noise keyed on (seed ^ 0x5DEECE66, tick, lane). Nothing here imports the system
under test or takes anything it made.

`dtype` selects the arithmetic: float32 is the configuration's precision;
the control computes the same steps in bfloat16.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_C_TICK = np.uint32(0x9E3779B9)
_C_LANE = np.uint32(0x85EBCA77)
_EXP_ONE = np.uint32(0x3F800000)
_DP_SALT = 0x5DEECE66

BFLOAT16 = np.dtype(ml_dtypes.bfloat16)


def u32(x) -> np.ndarray:
    """Any integer (array) folded to uint32, two's complement."""
    return (np.asarray(x, np.int64) & 0xFFFFFFFF).astype(np.uint32)


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    return h ^ (h >> np.uint32(16))


def uniform(seed, t, lane) -> np.ndarray:
    """Uniform in [0, 1) for (seed, tick, lane); broadcasts, float32."""
    with np.errstate(over="ignore"):    # uint32 arithmetic wraps by design
        h = fmix32(u32(seed) + u32(t) * _C_TICK)
        h = fmix32(h + u32(lane) * _C_LANE)
    mant = (h >> np.uint32(9)) | _EXP_ONE
    return mant.view(np.float32) - np.float32(1.0)


def frugal2u_step(m, step, sign, x, u, q):
    """One Frugal-2U tick per lane, in the dtype of `m` (branch-free)."""
    dt = m.dtype
    one = np.asarray(1, dt)
    x = np.asarray(x).astype(dt)
    u = np.asarray(u).astype(dt)
    q = np.asarray(q).astype(dt)
    up = (x > m) & (u > one - q)
    down = (x < m) & (u > q)
    step_u = step + np.where(sign > 0, one, -one).astype(dt)
    m_u = m + np.where(step_u > 0, np.ceil(step_u), one).astype(dt)
    over = m_u > x
    step_u = np.where(over, step_u + (x - m_u), step_u).astype(dt)
    m_u = np.where(over, x, m_u).astype(dt)
    step_u = np.where((sign < 0) & (step_u > 1), one, step_u).astype(dt)
    step_d = step + np.where(sign < 0, one, -one).astype(dt)
    m_d = m - np.where(step_d > 0, np.ceil(step_d), one).astype(dt)
    under = m_d < x
    step_d = np.where(under, step_d + (m_d - x), step_d).astype(dt)
    m_d = np.where(under, x, m_d).astype(dt)
    step_d = np.where((sign > 0) & (step_d > 1), one, step_d).astype(dt)
    new_m = np.where(up, m_u, np.where(down, m_d, m)).astype(dt)
    new_step = np.where(up, step_u, np.where(down, step_d, step)).astype(dt)
    new_sign = np.where(up, one, np.where(down, -one, sign)).astype(dt)
    return new_m, new_step, new_sign


def fresh_lanes(n: int, dtype=np.float32):
    """A lane's state before its first item: m = 0, step = 1, sign = +1."""
    return (np.zeros(n, dtype), np.ones(n, dtype), np.ones(n, dtype))


class DenseLanes:
    """Sampled lanes of a dense [T, G] fleet: lane = g * Q + qi."""

    def __init__(self, lanes, quantiles, seed, dtype=np.float32):
        self.lanes = np.asarray(lanes, np.int64)
        qs = np.asarray(quantiles, np.float32)
        self.groups = self.lanes // len(qs)
        self.q = qs[self.lanes % len(qs)]
        self.seed = int(seed)
        self.state = fresh_lanes(len(self.lanes), dtype)
        self.t = 0

    def ingest(self, items_of_groups: np.ndarray) -> None:
        """Apply a [T, n] block of the sampled lanes' group items."""
        m, step, sign = self.state
        for row in items_of_groups:
            u = uniform(self.seed, self.t, self.lanes)
            m, step, sign = frugal2u_step(m, step, sign, row, u, self.q)
            self.t += 1
        self.state = (m, step, sign)


def dp_release(m: np.ndarray, epsilon: float, seed, tick, lanes) -> np.ndarray:
    """m + Laplace(1 / epsilon), the noise keyed on (seed ^ salt, tick,
    lane) through the same counter hash; float32 result."""
    u = uniform((int(seed) & 0xFFFFFFFF) ^ _DP_SALT, tick,
                lanes).astype(np.float64)
    c = u - 0.5
    noise = -(1.0 / float(epsilon)) * np.sign(c) * np.log(
        np.maximum(1.0 - 2.0 * np.abs(c), np.finfo(np.float64).tiny))
    return (np.asarray(m, np.float64) + noise).astype(np.float32)
