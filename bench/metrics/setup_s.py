"""Process start to window start (s): weights, programs, warm-up."""


def read(run):
    return run.setup_s
