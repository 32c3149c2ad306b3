"""Comparisons that decide `correct`: each number beside its limit."""
from __future__ import annotations

import sys

import numpy as np


def words_differing(got, want) -> int:
    """Elements whose bits differ (NaNs compare by their bits); a shape or
    dtype mismatch counts every element. Counting form of chip_smoke.py's
    assert_bit_exact."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        return max(got.size, want.size, 1)
    view = {8: np.uint64, 4: np.uint32, 2: np.uint16}.get(got.dtype.itemsize,
                                                          np.uint8)
    return int(np.count_nonzero(got.view(view) != want.view(view)))


# Copied from chip_smoke.py (sample_blocks).
def sample_blocks(rng, num_lanes, blocks, width):
    """Seeded lane sample: `blocks` distinct [start, start + width) runs."""
    starts = rng.choice(num_lanes // width, size=blocks, replace=False)
    return sorted(int(s) * width for s in starts)


class Checks:
    """Numbers compared, each with its limit; correct when every number is
    at or under its limit."""

    def __init__(self):
        self.items = {}

    def add(self, name: str, value, limit) -> None:
        self.items[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in self.items.values())

    def print_stderr(self) -> None:
        for name, c in self.items.items():
            print(f"check {name} {c['value']} limit {c['limit']}",
                  file=sys.stderr, flush=True)
