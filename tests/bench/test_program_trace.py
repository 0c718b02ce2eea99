"""The reduction of what the program puts on a trace (``bench.program_trace``):
the protobuf wire-format reader on a hand-made XSpace, the readers on a
hand-made trace, and both on a trace recorded on a TPU v5e before the
program had spans and scopes, which they must read as before."""
import gzip
import pathlib

import pytest

from bench import block_flops, flops, program_trace, spec, trace
from bench.program_trace import ProgramTrace
from bench.trace import Device, Trace

DATA = pathlib.Path(__file__).parent / "data"


# -- a hand-made XSpace ---------------------------------------------------------
def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    data = value.encode() if isinstance(value, str) else value
    return varint(num << 3 | 2) + varint(len(data)) + data


def map_entry(num: int, key: int, value: bytes) -> bytes:
    return field(num, field(1, key) + field(2, value))


def plane(name: str, events: dict, stats: dict) -> bytes:
    """An XPlane: ``events`` id -> (name, display name, [(stat id, str
    or ref id)]); ``stats`` stat id -> name.  Its one line holds bytes
    that are no protobuf, which the reader must step over."""
    out = field(1, 5) + field(2, name) + field(3, b"\xff\xff\xff\x07 no")
    for eid, (ename, display, estats) in events.items():
        md = field(1, eid) + field(2, ename) + field(4, display)
        for sid, v in estats:
            md += field(5, field(1, sid)
                        + (field(5, v) if isinstance(v, str) else field(7, v)))
        out += map_entry(4, eid, md)
    for sid, sname in stats.items():
        out += map_entry(5, sid, field(1, sid) + field(2, sname))
    return out


def test_wire_reader_on_a_hand_made_xspace():
    stats = {3: "tf_op", 4: "jit(f)/mlp/dot_general:", 9: "flops"}
    dev = plane("/device:TPU:0", {
        7: ("%fusion.1 = f32[8] fusion()", "fusion.1",
            [(9, "12"), (3, "jit(f)/attn/exp:")]),
        8: ("%convolution.2 = f32[8] convolution()", "convolution.2",
            [(3, 4)]),  # the path interned as a stat metadata name
        11: ("%copy.3 = f32[8] copy()", "copy.3", [(9, "0")]),
    }, stats)
    host = plane("/host:CPU", {1: ("engine.run_once", "", [(3, "x")])},
                 stats)
    data = field(1, dev) + field(1, host) + field(4, "a host")
    assert program_trace.tf_op_paths(data) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": ("fusion.1", "jit(f)/attn/exp:"),
        "%convolution.2 = f32[8] convolution()":
            ("convolution.2", "jit(f)/mlp/dot_general:")}}


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/while/body/closed_call/attn/blhd,bkhd->bhlk/dot_general:",
     "attn"),
    ("jit(f)/while/body/closed_call/attn_out/...d,df->...f/dot_general:",
     "attn_out"),
    ("jit(f)/while/body/closed_call/qkv/concatenate:", "qkv"),
    ("jit(f)/while/body/closed_call/mlp/tanh:", "mlp"),
    ("jit(f)/mlp/attn/exp:", "attn"),  # the innermost scope
    ("jit(f)/while/body/closed_call/reduce_sum:", "other"),
    ("jit(f)/mlp_proj/attention/exp:", "other"),  # components, not prefixes
    ("", "other"),
])
def test_scope_of_a_path(path, scope):
    assert program_trace.scope_of(path) == scope


# -- a hand-made trace ----------------------------------------------------------
TINY = {"model": {"d_model": 64, "n_heads": 2, "head_dim": 32, "d_ff": 128,
                  "n_layers": 2},
        "text_tokens": 256, "sampler": {"num_steps": 2}}
PEAK = 1e9


def hand_made() -> ProgramTrace:
    # chip 0: two executions of the step; in each, qkv 1 s, attn 2 s,
    # attn_out 0.5 s, mlp 1 s, an unscoped op 0.5 s; between them a
    # host stall of 2 s inside engine.finish, and an arrival wait
    def step(t0):
        return [(t0, t0 + 1.0, "%q"), (t0 + 1.0, t0 + 3.0, "%a"),
                (t0 + 3.0, t0 + 3.5, "%o"), (t0 + 3.5, t0 + 4.5, "%m"),
                (t0 + 4.5, t0 + 5.0, "%n")]

    d0 = Device("/device:TPU:0", ops=step(1.0) + step(8.0) + [
        (14.0, 14.5, "%copy")],
        modules=[(1.0, 6.0, "jit_f(3)"), (8.0, 13.0, "jit_f(3)"),
                 (14.0, 14.5, "jit_fold_in(1)")])
    paths = {"%q": "jit(f)/qkv/x:", "%a": "jit(f)/attn/exp:",
             "%o": "jit(f)/attn_out/y:", "%m": "jit(f)/mlp/tanh:",
             "%n": "jit(f)/add:", "%copy": "jit(fold_in)/mlp/z:"}
    tags = {"rows": 2, "seq": 64}
    spans = [(0.5, 13.2, "engine.run_once", {}),
             (0.5, 0.9, "engine.admit", {}),
             (0.9, 1.0, "engine.dispatch", tags),
             (1.0, 1.1, "engine.dispatch", tags),
             (6.0, 7.9, "engine.finish", tags),
             (13.0, 13.2, "engine.sync", tags)]
    harness = [(0.4, 13.3, "bench.run_once"),
               (13.3, 14.0, "bench.wait_arrival")]
    return ProgramTrace(Trace([d0], harness), spans, [paths])


def test_scope_seconds_keep_to_the_step_executions():
    # the copy's path names ``mlp`` but it runs in another program
    assert program_trace.scope_seconds(hand_made()) == {
        "qkv": 2.0, "attn": 4.0, "attn_out": 1.0, "mlp": 2.0, "other": 1.0}


def test_part_mfu_of_a_hand_made_trace():
    pt = hand_made()
    got = program_trace.part_mfu(pt, TINY, PEAK)
    work = block_flops.block_flops(TINY, 2, 64)
    assert got == pytest.approx({
        "attn": 100 * 2 * work["attn"] / (4.0 * PEAK),
        "mlp": 100 * 2 * work["mlp"] / (2.0 * PEAK),
        "proj": 100 * 2 * work["proj"] / (3.0 * PEAK)})
    # a dispatch whose execution the trace's close cut off changes nothing
    cut = ProgramTrace(pt.trace, pt.spans + [
        (13.25, 13.3, "engine.dispatch", {"rows": 4, "seq": 64})], pt.paths)
    assert program_trace.part_mfu(cut, TINY, PEAK) == pytest.approx(got)
    # an execution with no dispatch before it (enqueued before the trace
    # began) counts neither its FLOPs nor its time; the next one takes
    # the tags of the latest dispatch before it
    late = ProgramTrace(pt.trace, [
        sp for sp in pt.spans if sp[2] != "engine.dispatch"] + [
        (7.0, 7.1, "engine.dispatch", {"rows": 1, "seq": 64}),
        (7.5, 7.6, "engine.dispatch", {"rows": 3, "seq": 64})], pt.paths)
    work3 = block_flops.block_flops(TINY, 3, 64)
    assert program_trace.part_mfu(late, TINY, PEAK) == pytest.approx({
        "attn": 100 * work3["attn"] / (2.0 * PEAK),
        "mlp": 100 * work3["mlp"] / (1.0 * PEAK),
        "proj": 100 * work3["proj"] / (1.5 * PEAK)})
    # with no scoped time (a program without scopes) no part reads
    bare = ProgramTrace(pt.trace, pt.spans, [{}])
    assert program_trace.part_mfu(bare, TINY, PEAK) == {}


def test_block_flops_are_parts_of_the_forward():
    """attn, mlp and proj are the forward's block matmuls bar the adaLN
    modulation."""
    config = {**TINY, "model": {**TINY["model"]},
              "sampler": {"guidance_scale": 3.0}}
    for rows, latent in ((1, 64), (3, 1024)):
        parts = block_flops.block_flops(config, rows, latent)
        m = config["model"]
        ada = 2 * rows * m["n_layers"] * 2 * m["d_model"] * 6 * m["d_model"]
        blocks = flops.step_flops(config, rows, latent) - 2 * (
            flops.forward_flops({**config, "model": {**m, "n_layers": 0}},
                                rows, latent))
        assert sum(parts.values()) + ada == pytest.approx(blocks)


def test_host_stall_and_gaps_are_named_by_the_program():
    pt = hand_made()
    # inside engine.run_once: 0.5-1.0 before the first op, 6.0-8.0 in
    # engine.finish, 13.0-13.2 after the last
    assert program_trace.host_stall_max_s(pt) == pytest.approx(2.0)
    assert program_trace.stall_gaps(pt, 2) == [
        ["engine.finish", pytest.approx(2.0)],
        ["engine.admit", pytest.approx(0.5)]]
    assert program_trace.idle_gaps(pt, 2) == [
        ["engine.finish", pytest.approx(2.0)],
        ["bench.wait_arrival", pytest.approx(1.0)]]
    # the harness's own spans alone name the same gaps as before
    assert trace.idle_gaps(pt.trace, 2) == [
        ["bench.run_once", pytest.approx(2.0)],
        ["bench.wait_arrival", pytest.approx(1.0)]]
    counts = program_trace.step_op_counts(pt)
    assert counts["ops_per_step"] == [5]
    assert counts["op_kinds"] == {"q": 2, "a": 2, "o": 2, "m": 2, "n": 2}


def test_a_trace_without_program_spans_reads_nothing():
    pt = hand_made()
    bare = ProgramTrace(pt.trace, [], [{}])
    assert program_trace.host_stall_max_s(bare) is None
    assert program_trace.stall_gaps(bare) == []
    assert program_trace.part_mfu(bare, TINY, PEAK) == {}
    assert program_trace.report(bare, TINY, PEAK)["metrics"] == {}
    empty = ProgramTrace(Trace([], []), pt.spans, [])
    assert program_trace.host_stall_max_s(empty) is None
    assert program_trace.idle_gaps(empty) == []


# -- traces recorded on a TPU v5e -------------------------------------------------
def unpacked(tmp_path, name: str) -> pathlib.Path:
    out = tmp_path / name.removesuffix(".gz")
    out.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return out


def test_a_trace_from_before_the_spans(tmp_path):
    """flux_img_mix_1s (recorded before the program had spans or
    scopes): the base reduction is ``bench.trace.load``'s, unchanged, the
    readers of this module find nothing to read, and the gaps keep the
    harness's names."""
    path = unpacked(tmp_path, "flux_img_mix_1s.xplane.pb.gz")
    pt = program_trace.load(path)
    t = trace.load(path)
    assert pt.trace == t
    assert pt.spans == []
    assert program_trace.idle_gaps(pt) == trace.idle_gaps(t)
    secs = program_trace.scope_seconds(pt)
    assert secs["other"] == pytest.approx(0.490180550999999)
    assert all(secs[s] == 0 for s in program_trace.SCOPES)
    cell = spec.cell("flux_img_mix")
    assert pt.chip == "TPU v5 Lite"
    out = program_trace.report(pt, cell.config, program_trace.peak_flops(pt))
    assert out["metrics"] == {}
    assert out["top_ops"] == trace.top_ops(t)
    assert out["ops_per_step"] == [867, 1043]
    assert device_names_match(path, pt)


def device_names_match(path, pt) -> bool:
    """The metadata each op's path came from is the op's own: its display
    name is the HLO name its event name starts with.  XLA leaves a few
    ops with no ``tf_op`` (async copies and slices, a few fusions it made
    itself): they are ``other``, under a tenth of the step's time."""
    paths = program_trace.tf_op_paths(path.read_bytes())
    (plane,) = paths.values()
    for name, (display, tf_op) in plane.items():
        assert name.startswith(f"%{display} = "), (name, display)
        assert tf_op.startswith("jit(")
    step = program_trace.step_ops(pt, 0)
    named = [(s, e, n) for s, e, n in step if n in plane]
    assert all(plane[n][1].startswith("jit(f)/") for _, _, n in named)
    share = sum(e - s for s, e, _ in named) / sum(e - s for s, e, _ in step)
    return share > 0.9
