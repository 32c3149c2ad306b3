"""Spread of the chips' device busy time over the traced window, in %:
(max - min) / mean of each chip's busy seconds, busy being the union of
its op intervals (yardstick/trace.py). 0 where every chip works alike; a
chip whose slab lands late or whose kernel runs longer shows here. None
with fewer than two chips in the trace."""
from yardstick import trace as tr


def read(run):
    td = run.trace
    if td is None or len(td.ops) < 2:
        return None
    busy = [tr.union_ns([(s, e) for _, s, e in evs])
            for evs in td.ops.values()]
    mean = sum(busy) / len(busy)
    if not mean:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
