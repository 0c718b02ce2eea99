"""Device idle time inside the harness run_once spans (%)."""
from bench import readers


def read(run):
    return readers.idle_share(run)
