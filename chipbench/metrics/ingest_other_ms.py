"""Device time per chunk of the ingest executable's ops other than the
dense kernel (the on-device Q-fold repeat, packing and copies), averaged
over the devices."""
from yardstick import trace as tr


def read(run):
    td = run.trace

    def per_device(d):
        mods = tr.matching(td.modules[d], tr.INGEST_MODULE)
        if not mods:
            return None
        kernels = set(td.kernels.get(d, []))
        ops = [ev for ev in tr.inside(td.ops[d], mods) if ev not in kernels]
        return tr.total_ms(ops) / len(mods)

    return tr.per_device_mean(td, per_device)
