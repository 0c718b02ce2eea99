"""Reduction of a profiler trace (``.xplane.pb``) to intervals and shares.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds every operation the chip ran and whose ``XLA Modules`` line
holds every program execution, and a host plane whose events include
the harness's ``jax.profiler.TraceAnnotation`` spans (``bench.*``).
Times here are in seconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."

Interval = tuple[float, float, str]  # (start s, end s, name)


@dataclasses.dataclass
class Device:
    name: str
    ops: list[Interval]
    modules: list[Interval]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[Interval]  # harness host spans


def find(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str | pathlib.Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}

            def ivs(line_name):
                ln = lines.get(line_name)
                return sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                              for e in ln.events) if ln else []

            devices.append(Device(plane.name, leaves(ivs(OPS_LINE)),
                                  ivs(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices, sorted(spans))


def leaves(ops: list[Interval]) -> list[Interval]:
    """The operations that hold no other: a ``while`` loop's event spans
    the events of its body, which are the work."""
    out = []
    for i, (s, e, name) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt and s <= nxt[0] < e and nxt[1] <= e:
            continue
        out.append((s, e, name))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", event_name.split(" = ")[0].lstrip("%"))


def union(ivs) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of the intervals."""
    out: list[list[float]] = []
    for s, e, *_ in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(cover: list[tuple[float, float]], s: float, e: float) -> float:
    """Seconds of [s, e] that the disjoint sorted ``cover`` covers."""
    i = max(bisect.bisect_right([c[0] for c in cover], s) - 1, 0)
    total = 0.0
    while i < len(cover) and cover[i][0] < e:
        total += max(0.0, min(e, cover[i][1]) - max(s, cover[i][0]))
        i += 1
    return total


def length(cover) -> float:
    return sum(e - s for s, e in cover)


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips (0
    when the trace holds no chip)."""
    if not trace.devices:
        return 0.0
    return sum(length(union(d.ops)) for d in trace.devices) / len(
        trace.devices)


def idle_share_within(trace: Trace, span_name: str) -> float | None:
    """Share of the time inside the host spans ``span_name`` in which a
    chip ran no operation, averaged over the chips; None without such
    spans."""
    spans = union([s for s in trace.spans if s[2] == span_name])
    total = length(spans)
    if total <= 0 or not trace.devices:
        return None
    idle = 0.0
    for d in trace.devices:
        busy = union(d.ops)
        idle += total - sum(covered(busy, s, e) for s, e in spans)
    return idle / len(trace.devices) / total


def module_runs(trace: Trace, name: re.Pattern) -> list[list[Interval]]:
    """Per chip, the executions of the programs whose name matches
    ``name``, in time order."""
    return [[m for m in d.modules if name.match(m[2])]
            for d in trace.devices]


def top_ops(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` operation names (``op_name``) with the most device
    time, summed over every chip."""
    tot: dict[str, float] = {}
    for d in trace.devices:
        for s, e, name in d.ops:
            key = op_name(name)
            tot[key] = tot.get(key, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between operations on chip 0 inside the
    window, each named by the innermost harness span open over its
    middle (``idle`` when none is)."""
    if not trace.devices:
        return []
    d = trace.devices[0]
    busy = union(d.ops)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_ = [sp for sp in trace.spans if sp[0] <= mid <= sp[1]]
        name = min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else "idle"
        out.append([name, e - s])
    return out
