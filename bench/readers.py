"""Arithmetic the metric readers share (``metrics/<name>.py`` each pick
one quantity from a ``bench.run.Run``)."""
from __future__ import annotations

import bisect
import math
import re

from bench import flops, trace

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
    if not shapes or not per_chip or any(len(m) != len(shapes)
                                         for m in per_chip):
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


# part of the form's block (``flops.part_flops``) -> the program's named
# scopes whose device time does its work
PART_SCOPES = {"attn": ("attn",), "mlp": ("mlp",),
               "proj": ("qkv", "attn_out")}
RUN_ONCE = "engine.run_once"
DISPATCH = "engine.dispatch"


def step_runs(tr: trace.Trace, chip: int) -> list[tuple[trace.Interval,
                                                       list]]:
    """Chip ``chip``'s executions of the sampler step (``STEP_MODULE``),
    each with the leaf ops that start in it."""
    d = tr.devices[chip]
    runs = [m for m in d.modules if STEP_MODULE.match(m[2])]
    starts = [m[0] for m in runs]
    ops: list[list[trace.Interval]] = [[] for _ in runs]
    for op in d.ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < runs[i][1]:
            ops[i].append(op)
    return list(zip(runs, ops))


def _add_scopes(tot: dict, ops, paths: dict) -> None:
    for s, e, name in ops:
        tot[trace.scope_of(paths.get(name, ""))] += e - s


def scope_seconds(tr: trace.Trace) -> dict[str, float]:
    """Device seconds of the step's leaf ops by scope (``trace.SCOPES``
    and ``other``), summed over the chips."""
    tot = dict.fromkeys((*trace.SCOPES, trace.OTHER), 0.0)
    for chip, paths in enumerate(tr.paths):
        for _, ops in step_runs(tr, chip):
            _add_scopes(tot, ops, paths)
    return tot


def part_mfu(tr: trace.Trace, config: dict, peak: float
             ) -> dict[str, float]:
    """Each part's model FLOPs (``flops.part_flops``) over its scopes'
    device time at ``peak`` FLOP/s a chip, in percent, over the step
    executions that an ``engine.dispatch`` span enqueued.  An execution
    takes the (rows, seq) tags of the latest dispatch span that started
    before it (every dispatch of one ``run_once`` has the batch's tags,
    and ``run_once`` waits for its last step), so a dispatch whose
    execution the trace's close cut off, or an execution enqueued before
    the trace began, counts neither FLOPs nor time.  A part whose scopes
    took no device time (a program without the scopes) is left out."""
    disp = [(s, t) for s, _e, n, t in tr.program
            if n == DISPATCH and "rows" in t and "seq" in t]
    starts = [s for s, _ in disp]
    work = dict.fromkeys(PART_SCOPES, 0.0)
    secs = dict.fromkeys((*trace.SCOPES, trace.OTHER), 0.0)
    for chip, paths in enumerate(tr.paths):
        for run, ops in step_runs(tr, chip):
            i = bisect.bisect_right(starts, run[0]) - 1
            if i < 0:
                continue
            tags = disp[i][1]
            # each chip does its share of the step's work
            for k, v in flops.part_flops(config, int(tags["rows"]),
                                         int(tags["seq"])).items():
                work[k] += v / len(tr.paths)
            _add_scopes(secs, ops, paths)
    out = {}
    for part, scopes in PART_SCOPES.items():
        t = sum(secs[s] for s in scopes)
        if t > 0:
            out[part] = 100.0 * work[part] / (t * peak)
    return out


def host_stall_max_s(tr: trace.Trace) -> float | None:
    """The longest stretch inside an ``engine.run_once`` span in which
    chip 0 ran no op; None without such spans or chips."""
    gaps = trace.gaps_within(tr, RUN_ONCE)
    if gaps is None:
        return None
    return max((e - s for s, e in gaps), default=0.0)


def stall_gaps(tr: trace.Trace, n: int = 5) -> list[list]:
    """The ``n`` longest chip-idle stretches inside ``engine.run_once``,
    each named by the innermost span open over its middle."""
    return trace.named(tr, trace.gaps_within(tr, RUN_ONCE) or [], n)
