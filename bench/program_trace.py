"""What the program puts on a profiler trace, beside what ``bench.trace``
reduces.

The serving program marks its own work in two ways (DESIGN.md §12):

* its tracker spans (``engine.run_once`` and, inside it, ``engine.admit``,
  ``engine.prepare``, ``engine.dispatch`` a sampler step, ``engine.sync``,
  ``engine.finish``; also ``plan_cache.trace`` and ``calibration.refit``)
  are profiler annotations on the host plane, tagged ``rows`` and
  ``seq``, on the clock of the chips' ops;
* the DiT block's named scopes (``qkv``, ``attn``, ``attn_out``, ``mlp``)
  are components of each chip op's ``tf_op`` path, a stat of the op's
  event metadata.

``jax.profiler.ProfileData`` shows event stats but not event-metadata
stats, so ``tf_op_paths`` reads the ``.xplane.pb`` protobuf itself with a
small wire-format decoder: per ``/device:TPU:*`` plane, only its event and
stat metadata, stepping over its lines by their length.

``load`` gives ``bench.trace.load``'s ``Trace`` unchanged together with
those spans and paths; the readers below turn them into per-scope device
time, the scopes' shares of peak, the longest chip-idle stretch inside a
``run_once`` and the idle gaps named by the innermost span of either kind.
On a trace with no program spans or scopes they read nothing.

    python -m bench.program_trace <trace dir or .xplane.pb> --workload <cell>

prints them for a trace kept with ``bench.run --trace 1 --trace-dir``.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import pathlib
import sys

from bench import block_flops, readers, spec, trace

PROGRAM_PREFIXES = ("engine.", "plan_cache.", "calibration.")
SCOPES = ("qkv", "attn", "attn_out", "mlp")
OTHER = "other"
# block_flops part -> the scopes whose device time does its work
PART_SCOPES = {"attn": ("attn",), "mlp": ("mlp",),
               "proj": ("qkv", "attn_out")}
RUN_ONCE = "engine.run_once"
DISPATCH = "engine.dispatch"
TF_OP = "tf_op"
DEVICE_PREFIX = "/device:TPU:"

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


@dataclasses.dataclass
class ProgramTrace:
    trace: trace.Trace  # what bench.trace.load gives
    spans: list[Span]  # the program's tracker spans, by start
    paths: list[dict[str, str]]  # per chip of trace.devices: op name -> tf_op
    chip: str = ""  # the device kind the first TPU plane names


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


# -- loading ------------------------------------------------------------------
def load(path: str | pathlib.Path) -> ProgramTrace:
    """An ``.xplane.pb`` file, read once: ``bench.trace.load``'s reduction
    (the same chips, ops, executions and ``bench.*`` spans), the
    program's spans with their tags and each chip op's ``tf_op`` path."""
    from jax.profiler import ProfileData

    data = pathlib.Path(path).read_bytes()
    pd = ProfileData.from_serialized_xspace(data)
    devices, harness, spans, chip = [], [], [], ""
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}

            def ivs(line_name):
                ln = lines.get(line_name)
                return sorted((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                              for e in ln.events) if ln else []

            devices.append(trace.Device(
                plane.name, trace.leaves(ivs(trace.OPS_LINE)),
                ivs(trace.MODULES_LINE)))
            chip = chip or dict(plane.stats).get("device_type_string", "")
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    iv = (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    if e.name.startswith(trace.SPAN_PREFIX):
                        harness.append(iv)
                    elif e.name.startswith(PROGRAM_PREFIXES):
                        spans.append((*iv, dict(e.stats)))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    by_plane = tf_op_paths(data)
    paths = [{k: v[1] for k, v in by_plane.get(d.name, {}).items()}
             for d in devices]
    return ProgramTrace(trace.Trace(devices, sorted(harness)),
                        sorted(spans, key=lambda sp: sp[:3]), paths, chip)


# -- readers ------------------------------------------------------------------
def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` among a ``tf_op`` path's components."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return OTHER


def step_runs(pt: ProgramTrace, chip: int) -> list[tuple[trace.Interval,
                                                        list]]:
    """Chip ``chip``'s executions of the sampler step
    (``readers.STEP_MODULE``), each with the leaf ops that start in it."""
    d = pt.trace.devices[chip]
    runs = [m for m in d.modules if readers.STEP_MODULE.match(m[2])]
    starts = [m[0] for m in runs]
    ops: list[list[trace.Interval]] = [[] for _ in runs]
    for op in d.ops:
        i = bisect.bisect_right(starts, op[0]) - 1
        if i >= 0 and op[0] < runs[i][1]:
            ops[i].append(op)
    return list(zip(runs, ops))


def step_ops(pt: ProgramTrace, chip: int) -> list[trace.Interval]:
    """Chip ``chip``'s leaf ops that start inside an execution of the
    sampler step."""
    return [op for _, ops in step_runs(pt, chip) for op in ops]


def _add_scopes(tot: dict, ops, paths: dict) -> None:
    for s, e, name in ops:
        tot[scope_of(paths.get(name, ""))] += e - s


def scope_seconds(pt: ProgramTrace) -> dict[str, float]:
    """Device seconds of the step's leaf ops by scope (``SCOPES`` and
    ``other``), summed over the chips."""
    tot = dict.fromkeys((*SCOPES, OTHER), 0.0)
    for chip, paths in enumerate(pt.paths):
        _add_scopes(tot, step_ops(pt, chip), paths)
    return tot


def part_mfu(pt: ProgramTrace, config: dict, peak: float
             ) -> dict[str, float]:
    """Each ``block_flops`` part's model FLOPs over its scopes' device
    time at ``peak`` FLOP/s a chip, in percent, over the step executions
    that an ``engine.dispatch`` span enqueued.  An execution takes the
    (rows, seq) tags of the latest dispatch span that started before it
    (every dispatch of one ``run_once`` has the batch's tags, and
    ``run_once`` waits for its last step), so a dispatch whose execution
    the trace's close cut off, or an execution enqueued before the trace
    began, counts neither FLOPs nor time.  A part whose scopes took no
    device time (a program without the scopes) is left out."""
    disp = [(s, t) for s, _e, n, t in pt.spans
            if n == DISPATCH and "rows" in t and "seq" in t]
    starts = [s for s, _ in disp]
    work = dict.fromkeys(block_flops.PARTS, 0.0)
    secs = dict.fromkeys((*SCOPES, OTHER), 0.0)
    for chip, paths in enumerate(pt.paths):
        for run, ops in step_runs(pt, chip):
            i = bisect.bisect_right(starts, run[0]) - 1
            if i < 0:
                continue
            tags = disp[i][1]
            # each chip does its share of the step's work
            for k, v in block_flops.block_flops(
                    config, int(tags["rows"]), int(tags["seq"])).items():
                work[k] += v / len(pt.paths)
            _add_scopes(secs, ops, paths)
    out = {}
    for part, scopes in PART_SCOPES.items():
        t = sum(secs[s] for s in scopes)
        if t > 0:
            out[part] = 100.0 * work[part] / (t * peak)
    return out


def _innermost(spans, t: float) -> str:
    """The name of the shortest span open at ``t`` (``idle`` if none)."""
    open_ = [sp for sp in spans if sp[0] <= t <= sp[1]]
    return min(open_, key=lambda sp: sp[1] - sp[0])[2] if open_ else "idle"


def _all_spans(pt: ProgramTrace) -> list[trace.Interval]:
    return pt.trace.spans + [sp[:3] for sp in pt.spans]


def run_once_gaps(pt: ProgramTrace) -> list[tuple[float, float]] | None:
    """Every stretch inside an ``engine.run_once`` span in which chip 0
    ran no op, longest first; None without such spans or chips."""
    runs = [sp for sp in pt.spans if sp[2] == RUN_ONCE]
    if not runs or not pt.trace.devices:
        return None
    busy = trace.union(pt.trace.devices[0].ops)
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
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_stall_max_s(pt: ProgramTrace) -> float | None:
    """The longest stretch inside an ``engine.run_once`` span in which
    chip 0 ran no op; None without such spans or chips."""
    gaps = run_once_gaps(pt)
    if gaps is None:
        return None
    return gaps[0][1] - gaps[0][0] if gaps else 0.0


def stall_gaps(pt: ProgramTrace, n: int = 5) -> list[list]:
    """The ``n`` longest chip-idle stretches inside ``engine.run_once``,
    each named by the innermost span open over its middle."""
    spans = _all_spans(pt)
    return [[_innermost(spans, (s + e) / 2), e - s]
            for s, e in (run_once_gaps(pt) or [])[:n]]


def idle_gaps(pt: ProgramTrace, n: int = 10) -> list[list]:
    """``bench.trace.idle_gaps`` with the program's spans beside the
    harness's: each of the ``n`` longest gaps between ops on chip 0,
    named by the innermost span of either kind open over its middle."""
    return trace.idle_gaps(trace.Trace(pt.trace.devices, _all_spans(pt)), n)


def step_op_counts(pt: ProgramTrace) -> dict:
    """Chip 0's sampler step, op by op: the number of leaf ops in each
    execution (the distinct counts) and the ops by kind (``op_name``)."""
    if not pt.trace.devices:
        return {}
    runs = step_runs(pt, 0)
    kinds: dict[str, int] = {}
    for _, ops in runs:
        for _s, _e, name in ops:
            kinds[trace.op_name(name)] = kinds.get(trace.op_name(name), 0) + 1
    return {"ops_per_step": sorted({len(ops) for _, ops in runs}),
            "op_kinds": dict(sorted(kinds.items(), key=lambda kv: -kv[1]))}


def peak_flops(pt: ProgramTrace) -> float:
    """``peaks.json``'s bf16 peak of the trace's chip (the trace spells
    the kind with other capitals than ``jax.Device.device_kind``)."""
    table = json.loads((spec.HERE / "peaks.json").read_text())["devices"]
    kind = next((k for k in table if k.lower() == pt.chip.lower()), pt.chip)
    return spec.peak(kind)["bf16_flops_per_s"]


def report(pt: ProgramTrace, config: dict, peak: float) -> dict:
    """The per-layer readings of a trace, named as the image cell's
    metrics are, with the scope seconds, the gaps and the step's ops."""
    mfu = part_mfu(pt, config, peak)
    metrics = {f"{part}_mfu.image": v for part, v in mfu.items()}
    stall = host_stall_max_s(pt)
    if stall is not None:
        metrics["host_stall_max_s.image"] = stall
    return {"metrics": metrics, "scope_s": scope_seconds(pt),
            "stall_gaps": stall_gaps(pt), "idle_gaps": idle_gaps(pt),
            "top_ops": trace.top_ops(pt.trace), **step_op_counts(pt)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb, or a directory holding one")
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    path = pathlib.Path(args.trace)
    if path.is_dir():
        path = trace.find(path)
    pt = load(path)
    out = report(pt, spec.cell(args.workload).config, peak_flops(pt))
    for scope, secs in out["scope_s"].items():
        print(f"scope {scope}: {secs!r} s", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
