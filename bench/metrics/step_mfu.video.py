"""Model FLOPs of the traced steps over their device time at peak (%)."""
from bench import readers


def read(run):
    return readers.step_mfu(run)
