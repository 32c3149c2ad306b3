"""Host time per applied chunk that the ingest pipeline's apply loop waits
for the next staged chunk: the program's `ingest.wait_staged` spans in
total, over its `ingest.apply` spans (yardstick/spans.py)."""
from yardstick import spans


def read(run):
    log = spans.records(run)
    if log is None:
        return None
    applied = spans.named(log, "ingest.apply")
    if not applied:
        return None
    return sum(spans.durations_ms(log, "ingest.wait_staged")) / len(applied)
