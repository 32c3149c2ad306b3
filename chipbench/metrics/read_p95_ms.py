"""p95 over every read made in the window, from the reader's call of
`StreamingService.query` to the answer in hand (host clock)."""
import numpy as np


def read(run):
    if run.read_ms is None or not len(run.read_ms):
        return None
    return float(np.percentile(run.read_ms, 95))
