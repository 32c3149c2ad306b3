"""Host time per chunk in which no ingest executable is outstanding: the
mean, over consecutive chunks, of the apply thread's time from the end of
one chunk's `ingest.block` span (the wait on its device work) to the
start of the next chunk's (yardstick/spans.py). Less `wait_staged_ms`, it is the
host's own round trip: publication, the loop and the dispatch."""
from yardstick import spans


def read(run):
    log = spans.records(run)
    if log is None:
        return None
    gaps = spans.gaps_ms(log, "ingest.block")
    return sum(gaps) / len(gaps) if gaps else None
