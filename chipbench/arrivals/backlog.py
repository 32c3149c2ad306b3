"""A consumer catching up on a backlog: every chunk is due at window start,
so the system never runs dry and the window measures its capacity."""
import numpy as np

# More chunks than any window can apply.
MAX_CHUNKS = 1 << 20


def due(traffic, seconds):
    return np.zeros(MAX_CHUNKS)
