"""90th-percentile latency (s) over the requests due in the window."""
from bench import readers


def read(run):
    lat = readers.latencies(run)
    return readers.percentile(lat, 90) if lat else None
