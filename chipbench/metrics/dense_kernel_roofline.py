"""Share of the HBM bound the dense kernel reaches, in %: the bytes the
update must move per chunk on one device (yardstick/roofline.py), over the
chip's HBM peak (yardstick/peaks.py), over the kernel's device time. A
bytes bound: no published VPU peak exists."""
from yardstick import peaks, roofline
from yardstick import trace as tr


def read(run):
    ms = tr.kernel_mean_ms(run.trace, tr.INGEST_MODULE)
    if not ms:
        return None
    cfg = run.config
    nbytes = roofline.dense_chunk_bytes(cfg["chunk_t"], cfg["num_groups"],
                                        len(cfg["quantiles"]),
                                        cfg["state_words"])
    bound_s = roofline.bytes_bound_s(
        nbytes, peaks.peaks_for(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * bound_s / (ms / 1e3)
