"""Share of the HBM bound the dense kernel reaches on one chip of the
lane-sharded fleet, in %: the bytes one chip's share of a chunk must move
(T x G/n items, its G/n x Q lanes of state read and written;
yardstick/roofline.py), over the chip's HBM peak (yardstick/peaks.py),
over `shard_kernel_ms`. A bytes bound: no published VPU peak exists."""
from yardstick import harness, peaks, roofline


def read(run):
    ms = harness.load_module("metrics", "shard_kernel_ms").read(run)
    if not ms:
        return None
    cfg = run.config
    shards = int(cfg["topology"]["lanes"])
    nbytes = roofline.dense_chunk_bytes(cfg["chunk_t"],
                                        cfg["num_groups"] // shards,
                                        len(cfg["quantiles"]),
                                        cfg["state_words"])
    bound_s = roofline.bytes_bound_s(
        nbytes, peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms / 1e3)
