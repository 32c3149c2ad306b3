"""Device time of one call of the dense Pallas kernel in the lane-sharded
ingest: the mean duration of the custom-call ops inside the
`jit_sharded_ingest` executable on each chip, averaged over the chips.
Each chip runs the kernel on its own [T, G/n] column slab."""
from yardstick import trace as tr

# The jitted shard_map of parallel/group_sharding.py.
MODULE = r"sharded_ingest"


def read(run):
    if run.trace is None:
        return None
    return tr.kernel_mean_ms(run.trace, MODULE)
