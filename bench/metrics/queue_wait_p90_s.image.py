"""90th percentile (s) of due time to the start of the serving run_once."""
from bench import readers


def read(run):
    waits = readers.queue_waits(run)
    return readers.percentile(waits, 90) if waits else None
