"""Device time of one call of the dense Pallas kernel: the mean duration of
the custom-call ops inside the ingest executable, averaged over the
devices."""
from yardstick import trace as tr


def read(run):
    return tr.kernel_mean_ms(run.trace, tr.INGEST_MODULE)
