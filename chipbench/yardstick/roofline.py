"""Bytes the dense update must move, whatever implements it.

Per [T, G] chunk into G * Q lanes of W state words: every item is read
once at its G columns, and every state word is read once and written once.
No published VPU peak exists for the chip, so the dense kernel's roofline
share is a bytes bound: these bytes over the HBM peak, over kernel time.
"""
from __future__ import annotations

WORD_BYTES = 4


def dense_chunk_bytes(chunk_t: int, groups: int, quantiles: int,
                      state_words: int) -> int:
    items = chunk_t * groups * WORD_BYTES
    state = 2 * groups * quantiles * state_words * WORD_BYTES
    return items + state


def bytes_bound_s(nbytes: float, hbm_bytes_per_s: float) -> float:
    """The least time the chip's HBM needs to move `nbytes`."""
    return float(nbytes) / float(hbm_bytes_per_s)
