"""Reduction of a profiler trace (``.xplane.pb``) to intervals and shares.

A TPU trace has one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line holds every operation the chip ran and whose ``XLA Modules`` line
holds every program execution, and a host plane whose events include two
kinds of ``jax.profiler.TraceAnnotation`` span: the harness's own
(``bench.*``) and the serving program's tracker spans
(``engine.run_once`` and, inside it, ``engine.admit``, ``engine.prepare``,
``engine.dispatch`` a sampler step, ``engine.sync``, ``engine.finish``;
also ``plan_cache.trace`` and ``calibration.refit``), tagged ``rows`` and
``seq``.  The DiT block's named scopes (``qkv``, ``attn``, ``attn_out``,
``mlp``) are components of each chip op's ``tf_op`` path, a stat of the
op's event metadata.

``jax.profiler.ProfileData`` shows event stats but not event-metadata
stats, so ``tf_op_paths`` reads the same bytes with a small wire-format
decoder: per ``/device:TPU:*`` plane, only its event and stat metadata,
stepping over its lines by their length.  ``load`` reads the file once.
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
PROGRAM_PREFIXES = ("engine.", "plan_cache.", "calibration.")
DEVICE_PREFIX = "/device:TPU:"
SCOPES = ("qkv", "attn", "attn_out", "mlp")
OTHER = "other"
TF_OP = "tf_op"

Interval = tuple[float, float, str]  # (start s, end s, name)
Span = tuple[float, float, str, dict]  # (start s, end s, name, tags)

# xplane.proto field numbers (XSpace.planes; XPlane.name, .event_metadata,
# .stat_metadata; a map entry's key and value; XEventMetadata.id, .name,
# .display_name, .stats; XStatMetadata.id, .name; XStat.metadata_id,
# .str_value, .ref_value)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_VALUE = 2
_EMD_NAME, _EMD_DISPLAY, _EMD_STATS = 2, 4, 5
_SMD_ID, _SMD_NAME = 1, 2
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7



# -- protobuf wire format -----------------------------------------------------
def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of each field of the message in
    ``buf[start:end]``: an int for a varint, a (start, end) range for a
    length-delimited field, whose bytes are not read."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, rng) -> str:
    return bytes(buf[rng[0]:rng[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    """The value ranges of a protobuf map's entries."""
    for rng in entries:
        for f, v in _fields(buf, *rng):
            if f == _MAP_VALUE:
                yield v


def _plane_paths(buf, event_md, stat_md) -> dict[str, tuple[str, str]]:
    """Event metadata name -> (display name, tf_op) of one plane."""
    stat_names = {}
    for rng in _map_values(buf, stat_md):
        md = dict(_fields(buf, *rng))
        if _SMD_ID in md and _SMD_NAME in md:
            stat_names[md[_SMD_ID]] = _text(buf, md[_SMD_NAME])
    tf_op_ids = {k for k, v in stat_names.items() if v == TF_OP}
    out = {}
    for rng in _map_values(buf, event_md):
        name = display = path = None
        for f, v in _fields(buf, *rng):
            if f == _EMD_NAME:
                name = _text(buf, v)
            elif f == _EMD_DISPLAY:
                display = _text(buf, v)
            elif f == _EMD_STATS:
                stat = dict(_fields(buf, *v))
                if stat.get(_STAT_MD_ID) not in tf_op_ids:
                    continue
                if _STAT_STR in stat:
                    path = _text(buf, stat[_STAT_STR])
                elif _STAT_REF in stat:
                    path = stat_names.get(stat[_STAT_REF])
        if name is not None and path is not None:
            out[name] = (display or "", path)
    return out


def tf_op_paths(data: bytes) -> dict[str, dict[str, tuple[str, str]]]:
    """Per ``/device:TPU:*`` plane of a serialized XSpace: each event
    metadata's name (which is the name of the plane's events) ->
    (display name, ``tf_op`` path)."""
    buf = memoryview(data)
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        name, event_md, stat_md = "", [], []
        for pf, v in _fields(buf, *plane):
            if pf == _PLANE_NAME:
                name = _text(buf, v)
            elif pf == _PLANE_EVENT_MD:
                event_md.append(v)
            elif pf == _PLANE_STAT_MD:
                stat_md.append(v)
        if name.startswith(DEVICE_PREFIX):
            out[name] = _plane_paths(buf, event_md, stat_md)
    return out



@dataclasses.dataclass
class Device:
    name: str
    ops: list[Interval]
    modules: list[Interval]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[Interval]  # harness host spans
    program: list[Span] = dataclasses.field(default_factory=list)
    # per chip of ``devices``: op event name -> its ``tf_op`` path
    paths: list[dict[str, str]] = dataclasses.field(default_factory=list)


def find(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str | pathlib.Path) -> Trace:
    """An ``.xplane.pb`` file: each chip's leaf ops and program
    executions, the harness's and the program's host spans, and each chip
    op's ``tf_op`` path."""
    from jax.profiler import ProfileData

    data = pathlib.Path(path).read_bytes()
    pd = ProfileData.from_serialized_xspace(data)
    devices, harness, program = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}

            def ivs(line_name):
                ln = lines.get(line_name)
                return sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                              for e in ln.events) if ln else []

            devices.append(Device(plane.name, leaves(ivs(OPS_LINE)),
                                  ivs(MODULES_LINE)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    name = e.name
                    if name.startswith(SPAN_PREFIX):
                        harness.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                        name))
                    elif name.startswith(PROGRAM_PREFIXES):
                        program.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                        name, dict(e.stats)))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    by_plane = tf_op_paths(data)
    paths = [{k: v[1] for k, v in by_plane.get(d.name, {}).items()}
             for d in devices]
    return Trace(devices, sorted(harness),
                 sorted(program, key=lambda sp: sp[:3]), paths)


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


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` among a ``tf_op`` path's components."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return OTHER


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


def _innermost(spans, t: float) -> str:
    """The name of the shortest span open at ``t`` (``idle`` if none)."""
    open_ = [sp for sp in spans if sp[0] <= t <= sp[1]]
    return min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else "idle"


def named(trace: Trace, gaps, n: int) -> list[list]:
    """The ``n`` longest of ``gaps`` [(start, end)], each named by the
    innermost host span of either kind open over its middle."""
    spans = trace.spans + [sp[:3] for sp in trace.program]
    return [[_innermost(spans, (s + e) / 2), e - s]
            for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps between operations on chip 0, named by
    the innermost span open over their middle."""
    if not trace.devices:
        return []
    busy = union(trace.devices[0].ops)
    return named(trace, [(e0, s1) for (_, e0), (s1, _) in
                         zip(busy, busy[1:])], n)


def gaps_within(trace: Trace, span_name: str
                ) -> list[tuple[float, float]] | None:
    """Every stretch inside a program span ``span_name`` in which chip 0
    ran no op; None without such spans or chips."""
    runs = [sp for sp in trace.program if sp[2] == span_name]
    if not runs or not trace.devices:
        return None
    busy = union(trace.devices[0].ops)
    starts = [b[0] for b in busy]
    gaps = []
    for s, e, *_ in runs:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        cursor = s
        while i < len(busy) and busy[i][0] < e:
            if busy[i][0] > cursor:
                gaps.append((cursor, busy[i][0]))
            cursor = max(cursor, busy[i][1])
            i += 1
        if cursor < e:
            gaps.append((cursor, e))
    return gaps
