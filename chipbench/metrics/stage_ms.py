"""Median host time the ingest pipeline's put-ahead thread takes to stage
one [T, G] chunk on the device: the program's `ingest.stage` span around
`jax.device_put` (yardstick/spans.py). The call returns once the copy is
under way, so this is the host's part of staging, not the copy."""
from yardstick import spans


def read(run):
    return spans.median_ms(run, "ingest.stage")
