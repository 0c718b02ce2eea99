"""Longest stretch (s) inside an engine.run_once span with chip 0 idle."""
from bench import readers


def read(run):
    return None if run.trace is None else readers.host_stall_max_s(run.trace)
