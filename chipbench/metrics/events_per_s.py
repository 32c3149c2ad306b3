"""Events made visible during the window, over the whole window (host clock):
ticks x groups of each chunk published in the window."""


def read(run):
    if not run.window_s:
        return None
    return run.events_visible / run.window_s
