"""Median host time of a read's snapshot: the program's `query.snapshot`
span, the fleet version's query planes and cursor copied to the host
(yardstick/spans.py)."""
from yardstick import spans


def read(run):
    return spans.median_ms(run, "query.snapshot")
