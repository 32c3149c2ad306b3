"""Roofline-driven (block_g, block_t) autotuner for the program kernels.

Deterministic and model-driven — no on-device timing sweep. Candidate
blockings are enumerated over powers of two, filtered by the VMEM
residency budget (the HwSpec's, capped at the scoped limit the DMA kernel
requests: item slots + padded, double-buffered state blocks must fit), and
scored by kernel_model.predict_kernel's predicted wall time; the argmin
wins with a deterministic tie-break toward larger block_t (state-traffic
amortization) then larger block_g (fewer DMA issues).

Results are cached per (family_base, layout, platform/hw, g, t, q) via
lru_cache, so `frugal_update_auto` and FleetSpec users pay the model once
per shape class and get tuned blocks with zero API change. On hardware the
registry doesn't know (HwSpec 'unknown') the tuner does NOT guess a
prediction — it returns the repo's default blocking unchanged.

Bit-exactness: blocking only changes the grid/chunk walk, never the
update math — the counter-hash RNG keys on absolute (tick, lane), so tuned
blocks are just another chunking. tests/test_roofline.py pins tuned-vs-
default equality across the whole program registry via the conftest sweep.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

from repro.roofline.analysis import HwSpec, detect_hw, hw_for
from repro.roofline.kernel_model import (
    VMEM_LIMIT_BYTES, predict_kernel, vmem_footprint_bytes)

# the repo-wide default blocking (kernels/frugal_update.py signature)
DEFAULT_BLOCK_G = 128
DEFAULT_BLOCK_T = 256

_BLOCK_G_CANDIDATES = (128, 256, 512, 1024, 2048, 4096, 8192)
_BLOCK_T_CANDIDATES = (64, 128, 256, 512, 1024, 2048, 4096)


def _pow2_at_most(cands, limit: int):
    out = [c for c in cands if c <= limit]
    return out or [cands[0]]


@functools.lru_cache(maxsize=1024)
def _tuned(family_base_name: str, layout, hw_name: str,
           g: int, t: int, q: int) -> Tuple[int, int]:
    hw = hw_for(hw_name)
    if not hw.known:
        return (DEFAULT_BLOCK_G, DEFAULT_BLOCK_T)
    g_eff = max(g * q, 1)
    vmem_budget = min(hw.vmem_bytes, VMEM_LIMIT_BYTES)
    best = None
    for bg in _pow2_at_most(_BLOCK_G_CANDIDATES, g_eff):
        for bt in _pow2_at_most(_BLOCK_T_CANDIDATES, max(t, 1)):
            if vmem_footprint_bytes(layout, block_g=bg,
                                    block_t=bt) > vmem_budget:
                continue
            # keep enough lane blocks to occupy every core
            if math.ceil(g_eff / bg) < hw.cores and bg > _BLOCK_G_CANDIDATES[0]:
                continue
            pred = predict_kernel(g, t, q, layout, block_g=bg, block_t=bt,
                                  hw=hw)
            key = (pred["predicted_s"], -bt, -bg)
            if best is None or key < best[0]:
                best = (key, (bg, bt))
    if best is None:  # nothing fits VMEM — smallest candidate blocking
        return (_BLOCK_G_CANDIDATES[0], _BLOCK_T_CANDIDATES[0])
    return best[1]


def autotune_blocks(program, g: int, t: int, q: int = 1, *,
                    hw: Optional[HwSpec] = None) -> Tuple[int, int]:
    """Tuned (block_g, block_t) for running `program` over G lanes ×
    Q quantiles × T ticks on `hw` (default: the detected local device).

    Cached per (family_base, layout, hw, g, t, q); the family_base keying
    means parameter variants of one family (decay rates, window sizes)
    share a tuning entry, matching how the kernels compile."""
    from repro.core.program import family_base

    hw = hw or detect_hw()
    base = family_base(program.family)
    return _tuned(base.family, program.layout, hw.name,
                  int(g), int(t), int(q))


def autotune_cache_info():
    """lru_cache statistics — test seam for hit/miss behavior."""
    return _tuned.cache_info()


def clear_autotune_cache() -> None:
    _tuned.cache_clear()
