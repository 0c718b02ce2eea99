"""Arithmetic the metric readers share (``metrics/<name>.py`` each pick
one quantity from a ``bench.run.Run``)."""
from __future__ import annotations

import math
import re

from bench import trace

# the served sampler step is jitted from a function named ``f``
STEP_MODULE = re.compile(r"^jit_f(\(|$)")


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile (p in [0, 100])."""
    v = sorted(values)
    return v[max(0, math.ceil(p / 100 * len(v)) - 1)]


def latencies(run) -> list[float]:
    """Results back minus due time, for every request due in the
    window; a request not back by the end of the drain counts as
    infinitely late."""
    return [(r.done - r.due) if r.done is not None else math.inf
            for r in run.requests if r.due < run.seconds]


def queue_waits(run) -> list[float]:
    """Due time to the start of the ``run_once`` that served it."""
    return [r.started - r.due for r in run.requests
            if r.due < run.seconds and r.started is not None]


def step_mfu(run) -> float | None:
    """Model FLOPs of the traced sampler steps over the chips' time in
    those steps at peak bf16 rate, in percent.  The steps are matched to
    the host's batches in order; None when the counts differ."""
    if run.trace is None:
        return None
    shapes = [(b.rows, b.length) for b in run.traced_batches
              for _ in range(run.num_steps)]
    per_chip = trace.module_runs(run.trace, STEP_MODULE)
    if not shapes or any(len(m) != len(shapes) for m in per_chip):
        return None
    work = sum(run.step_flops(*s) for s in shapes)
    secs = sum(e - s for runs in per_chip for s, e, _ in runs)
    return 100.0 * work / (secs * run.peak["bf16_flops_per_s"])


def idle_share(run) -> float | None:
    """Percent of the time inside ``run_once`` with no device operation."""
    if run.trace is None:
        return None
    share = trace.idle_share_within(run.trace, "bench.run_once")
    return None if share is None else 100.0 * share
