"""Bandwidth model of the program kernel family.

The paper's claim is that a frugal update is so small that throughput is
pure memory bandwidth; this module prices that bound for a concrete
(G, Q, StateLayout) against a registered HwSpec so the autotuner and the
e16 gate have a machine-independent denominator.

Traffic model for one dense update of T ticks over G lanes × Q quantiles
(the auto facade replicates lanes per quantile, so g_eff = G·Q), with the
kernel gridded (g_blocks, t_blocks) = (⌈g_eff/block_g⌉, ⌈T/block_t⌉):

  items   T · g_eff · 4B            read exactly once (DMA'd HBM→VMEM)
  state   2 · g_eff · W · 4B · t_blocks
          W = layout.num_words; the state planes are VMEM-resident within
          one t-block but must round-trip HBM at every t-block boundary
          (grid revisit), so larger block_t amortizes state traffic
  out     g_eff · 4B                final quantile estimates (negligible)

Fixed overheads (HwSpec.grid_step_s / dma_issue_s) are charged per grid
step and per DMA issue, divided across `cores` parallel executors —
they are what stops the tuner from always choosing the smallest tiles.

All predictions go through HwSpec.require_known(): an unrecognized device
raises RooflineUnknownHardware instead of pricing against guessed numbers.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

from repro.roofline.analysis import HwSpec, detect_hw

ITEM_BYTES = 4          # float32 stream items
WORD_BYTES = 4          # int32/float32 packed state words
# Scoped VMEM the DMA kernel asks Mosaic for (CompilerParams
# .vmem_limit_bytes), and so the most the autotuner may plan to fill.
VMEM_LIMIT_BYTES = 32 * 2**20


def kernel_bytes_per_item(layout, q: int = 1, *,
                          block_t: int, t: int) -> float:
    """Analytic HBM bytes moved per source item (per-lane, per-tick).

    Per item the kernel reads the item once per quantile replica and
    round-trips the packed state words once per t-block the item's tick
    range spans. Independent of G and block_g — lane blocking only changes
    grid shape, not traffic."""
    t_blocks = max(math.ceil(t / block_t), 1)
    item_b = q * ITEM_BYTES
    state_b = q * 2 * layout.num_words * WORD_BYTES * t_blocks / max(t, 1)
    return item_b + state_b


def kernel_bytes_total(g: int, t: int, q: int, layout, *,
                       block_t: int) -> float:
    """Total HBM bytes for one dense update (see module docstring)."""
    g_eff = g * q
    per_item = kernel_bytes_per_item(layout, q=1, block_t=block_t, t=t)
    return t * g_eff * per_item + g_eff * ITEM_BYTES  # + final estimates


def vmem_footprint_bytes(layout, *, block_g: int, block_t: int) -> int:
    """VMEM bytes one grid cell of the DMA kernel keeps resident: the two
    item slots, then the pipelined [1, block_g] blocks (quantile, state
    words in and out), each double-buffered and padded to the 8 sublanes
    of a 32-bit VMEM tile, then the seed/meta scalars."""
    items = 2 * block_t * block_g * ITEM_BYTES
    blocks = 1 + 2 * layout.num_words
    state = 2 * blocks * 8 * block_g * WORD_BYTES
    return items + state + 256


def predict_kernel(g: int, t: int, q: int, layout, *,
                   block_g: int, block_t: int,
                   hw: Optional[HwSpec] = None) -> Dict[str, float]:
    """Roofline prediction for one dense update at the given blocking.

    Returns bytes moved, the pure-bandwidth time bound, the fixed-overhead
    terms, and predicted items/s (items = T·G real source items; quantile
    replication is priced as traffic, not credited as throughput)."""
    hw = (hw or detect_hw()).require_known()
    g_eff = g * q
    g_blocks = max(math.ceil(g_eff / block_g), 1)
    t_blocks = max(math.ceil(t / block_t), 1)

    bytes_total = kernel_bytes_total(g, t, q, layout, block_t=block_t)
    bandwidth_s = bytes_total / hw.hbm_bw
    # grid cells run `cores`-wide; each sequential step and each DMA issue
    # pays its fixed cost on the critical path of one core's cell stream
    steps_per_core = math.ceil(g_blocks / max(hw.cores, 1)) * t_blocks
    overhead_s = steps_per_core * (hw.grid_step_s + hw.dma_issue_s)
    predicted_s = bandwidth_s + overhead_s

    items = t * g
    return {
        "hw": hw.name,
        "hw_nominal": hw.nominal,
        "g": g, "t": t, "q": q, "layout_words": layout.num_words,
        "block_g": block_g, "block_t": block_t,
        "grid": [g_blocks, t_blocks],
        "bytes_total": bytes_total,
        "bytes_per_item": bytes_total / max(items, 1),
        "bandwidth_s": bandwidth_s,
        "overhead_s": overhead_s,
        "predicted_s": predicted_s,
        "items_per_s_bound": items / bandwidth_s if bandwidth_s else 0.0,
        "items_per_s_predicted": items / predicted_s if predicted_s else 0.0,
        "vmem_bytes": vmem_footprint_bytes(layout, block_g=block_g,
                                           block_t=block_t),
    }
