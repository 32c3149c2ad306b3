"""Median host time of a DP tenant's release: the program's
`query.dp_release` span, the `2u-dp` Laplace release over every lane
after the plain release (yardstick/spans.py)."""
from yardstick import spans


def read(run):
    return spans.median_ms(run, "query.dp_release")
