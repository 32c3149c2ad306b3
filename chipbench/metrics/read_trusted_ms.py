"""Median host time of a trusted read in the window, call to answer in
hand: the snapshot's copy of the query plane and the plain query (host
clock)."""
import numpy as np


def read(run):
    spans = run.host_spans_ms.get("read_trusted")
    if spans is None or not len(spans):
        return None
    return float(np.median(spans))
