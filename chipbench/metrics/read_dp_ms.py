"""Median host time of a read by a differentially private tenant in the
window, call to answer in hand: the snapshot, the plain query and the
Laplace release over every lane (host clock)."""
import numpy as np


def read(run):
    spans = run.host_spans_ms.get("read_dp")
    if spans is None or not len(spans):
        return None
    return float(np.median(spans))
