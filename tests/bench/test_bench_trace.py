"""Trace reduction: interval arithmetic on a hand-made trace, the
protobuf wire-format reader on a hand-made XSpace, the readers of the
program's spans and scopes on a hand-made trace, the whole reduction on a
small trace recorded on a TPU v5e (before the program had spans and
scopes, which the readers must then read as nothing), and a mix's
``trace_s`` bounding the batches a traced run reads."""
import gzip
import pathlib

import pytest

from bench import flops, readers, spec, trace
from bench.run import Batch, Run, traced_batches
from bench.trace import Device, Trace

DATA = pathlib.Path(__file__).parent / "data"
FORM = spec.form("dit_uniform")


def hand_made_ops() -> Trace:
    # chip 0: compute 0-2, all-to-all 2-3 (exposed), compute 3-5 with an
    # all-to-all 4-4.5 under it, idle 5-6, compute 6-7
    d0 = Device("/device:TPU:0",
                ops=[(0.0, 2.0, "fusion.1"), (2.0, 3.0, "all-to-all.2"),
                     (3.0, 5.0, "convolution.3"), (4.0, 4.5, "all-to-all.4"),
                     (6.0, 7.0, "fusion.1")],
                modules=[(0.0, 3.0, "jit_f(11)"), (3.0, 7.0, "jit_f(11)"),
                         (5.2, 5.3, "jit_fold_in(2)")])
    return Trace([d0], spans=[(0.0, 7.0, "bench.run_once"),
                              (5.0, 6.0, "bench.submit")])


def test_union_and_cover():
    assert trace.union([(0, 1, "a"), (0.5, 2, "b"), (3, 4, "c")]) == [
        (0, 2), (3, 4)]
    assert trace.covered([(0, 2), (3, 4)], 1, 3.5) == pytest.approx(1.5)


def test_shares_of_a_hand_made_trace():
    t = hand_made_ops()
    assert trace.busy_s(t) == pytest.approx(6.0)
    assert trace.idle_share_within(t, "bench.run_once") == pytest.approx(
        1 / 7)
    assert [len(r) for r in trace.module_runs(t, readers.STEP_MODULE)] == [2]
    assert trace.idle_gaps(t) == [["bench.submit", pytest.approx(1.0)]]
    top = dict(trace.top_ops(t))
    assert top["fusion"] == pytest.approx(3.0)
    assert top["all-to-all"] == pytest.approx(1.5)


def test_a_trace_without_chips_reads_nothing():
    t = Trace([], [(0.0, 1.0, "bench.run_once")])
    assert trace.busy_s(t) == 0.0
    assert trace.idle_share_within(t, "bench.run_once") is None
    assert trace.idle_gaps(t) == []
    # a CPU run's trace: the device metrics read nothing, and no device
    # kind is looked up in the table of peaks
    run = Run(spec.cell("flux_img_mix"), 1.0, 0.0, [], [],
              [Batch(0, 0, 1, 1024)], t, "cpu", 1, 0)
    for name in ("step_mfu.image", "attn_mfu.image", "mlp_mfu.image",
                 "proj_mfu.image", "host_stall_max_s.image"):
        assert spec.metric_reader(name)(run) is None


def recorded(tmp_path, name: str) -> Trace:
    out = tmp_path / name.removesuffix(".gz")
    out.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return trace.load(out)


def test_a_recorded_one_chip_trace(tmp_path):
    """flux_img_mix, seed 31, ``--seconds 1 --trace 1`` on a TPU v5e: two
    one-row batches (2304 and 1024 latent tokens) of four steps.  The
    run printed step_mfu.image 64.37954101843742 and
    device_idle_share.image 2.139739786289274."""
    t = recorded(tmp_path, "flux_img_mix_1s.xplane.pb.gz")
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert {s[2] for s in t.spans} == {"bench.wait_arrival", "bench.submit",
                                       "bench.run_once"}
    assert trace.busy_s(t) == pytest.approx(0.4902110279999995)
    top = trace.top_ops(t, 3)
    assert top[0][0] == "fusion" and len(top) == 3
    run = Run(spec.cell("flux_img_mix"), 1.0, 0.0, [], [],
              [Batch(0, 0, 1, 2304), Batch(0, 0, 1, 1024)], t,
              "TPU v5 lite", 1, 0)
    assert readers.step_mfu(run) == pytest.approx(64.37954101843742)
    assert readers.idle_share(run) == pytest.approx(2.139739786289274)
    run.traced_batches.pop()  # the steps no longer match the batches
    assert readers.step_mfu(run) is None


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
    assert trace.tf_op_paths(data) == {"/device:TPU:0": {
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
    assert trace.scope_of(path) == scope


# -- a hand-made trace with the program's spans and scopes ------------------------
TINY = {"form": "dit_uniform",
        "model": {"d_model": 64, "n_heads": 2, "head_dim": 32, "d_ff": 128,
                  "n_layers": 2},
        "text_tokens": 256, "text_width": 64, "latent_channels": 64,
        "sampler": {"num_steps": 2}}
PEAK = 1e9


def hand_made() -> Trace:
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
    return Trace([d0], harness, spans, [paths])


def with_program(t: Trace, program, paths=None) -> Trace:
    return Trace(t.devices, t.spans, program,
                 t.paths if paths is None else paths)


def test_scope_seconds_keep_to_the_step_executions():
    # the copy's path names ``mlp`` but it runs in another program
    assert readers.scope_seconds(hand_made()) == {
        "qkv": 2.0, "attn": 4.0, "attn_out": 1.0, "mlp": 2.0, "other": 1.0}


def test_part_mfu_of_a_hand_made_trace():
    t = hand_made()
    got = readers.part_mfu(t, TINY, PEAK)
    work = flops.part_flops(TINY, 2, 64)
    assert got == pytest.approx({
        "attn": 100 * 2 * work["attn"] / (4.0 * PEAK),
        "mlp": 100 * 2 * work["mlp"] / (2.0 * PEAK),
        "proj": 100 * 2 * work["proj"] / (3.0 * PEAK)})
    # a dispatch whose execution the trace's close cut off changes nothing
    cut = with_program(t, t.program + [
        (13.25, 13.3, "engine.dispatch", {"rows": 4, "seq": 64})])
    assert readers.part_mfu(cut, TINY, PEAK) == pytest.approx(got)
    # an execution with no dispatch before it (enqueued before the trace
    # began) counts neither its FLOPs nor its time; the next one takes
    # the tags of the latest dispatch before it
    late = with_program(t, [
        sp for sp in t.program if sp[2] != "engine.dispatch"] + [
        (7.0, 7.1, "engine.dispatch", {"rows": 1, "seq": 64}),
        (7.5, 7.6, "engine.dispatch", {"rows": 3, "seq": 64})])
    work3 = flops.part_flops(TINY, 3, 64)
    assert readers.part_mfu(late, TINY, PEAK) == pytest.approx({
        "attn": 100 * work3["attn"] / (2.0 * PEAK),
        "mlp": 100 * work3["mlp"] / (1.0 * PEAK),
        "proj": 100 * work3["proj"] / (1.5 * PEAK)})
    # with no scoped time (a program without scopes) no part reads
    bare = with_program(t, t.program, [{}])
    assert readers.part_mfu(bare, TINY, PEAK) == {}


def test_block_flops_are_parts_of_the_forward():
    """attn, mlp and proj are the forward's block matmuls bar the adaLN
    modulation."""
    config = {**TINY, "model": {**TINY["model"]},
              "sampler": {"guidance_scale": 3.0}}
    for rows, latent in ((1, 64), (3, 1024)):
        parts = flops.part_flops(config, rows, latent)
        m = config["model"]
        ada = 2 * rows * m["n_layers"] * 2 * m["d_model"] * 6 * m["d_model"]
        blocks = flops.step_flops(config, rows, latent) - 2 * (
            FORM.forward_flops({**config, "model": {**m, "n_layers": 0}},
                               rows, latent))
        assert sum(parts.values()) + ada == pytest.approx(blocks)


def test_host_stall_and_gaps_are_named_by_the_program():
    t = hand_made()
    # inside engine.run_once: 0.5-1.0 before the first op, 6.0-8.0 in
    # engine.finish, 13.0-13.2 after the last
    assert readers.host_stall_max_s(t) == pytest.approx(2.0)
    assert readers.stall_gaps(t, 2) == [
        ["engine.finish", pytest.approx(2.0)],
        ["engine.admit", pytest.approx(0.5)]]
    assert trace.idle_gaps(t, 2) == [
        ["engine.finish", pytest.approx(2.0)],
        ["bench.wait_arrival", pytest.approx(1.0)]]
    # the harness's own spans alone name the same gaps as before
    assert trace.idle_gaps(with_program(t, []), 2) == [
        ["bench.run_once", pytest.approx(2.0)],
        ["bench.wait_arrival", pytest.approx(1.0)]]
    assert [len(ops) for _, ops in readers.step_runs(t, 0)] == [5, 5]


def test_a_trace_without_program_spans_reads_nothing():
    t = hand_made()
    bare = with_program(t, [], [{}])
    assert readers.host_stall_max_s(bare) is None
    assert readers.stall_gaps(bare) == []
    assert readers.part_mfu(bare, TINY, PEAK) == {}
    run = Run(spec.cell("flux_img_mix"), 1.0, 0.0, [], [], [], bare,
              "TPU v5 lite", 1, 0)
    for name in ("attn_mfu.image", "mlp_mfu.image", "proj_mfu.image",
                 "host_stall_max_s.image"):
        assert spec.metric_reader(name)(run) is None
    empty = Trace([], [], t.program, [])
    assert readers.host_stall_max_s(empty) is None
    assert trace.idle_gaps(empty) == []


# -- the recorded trace: what the program put on it ---------------------------------
def unpacked(tmp_path, name: str) -> pathlib.Path:
    out = tmp_path / name.removesuffix(".gz")
    out.write_bytes(gzip.decompress((DATA / name).read_bytes()))
    return out


def test_a_trace_from_before_the_spans(tmp_path):
    """flux_img_mix_1s (recorded before the program had spans or
    scopes): no program spans, the readers of them find nothing to read,
    and the gaps keep the harness's names."""
    path = unpacked(tmp_path, "flux_img_mix_1s.xplane.pb.gz")
    t = trace.load(path)
    assert t.program == []
    assert trace.idle_gaps(t) == trace.idle_gaps(with_program(t, []))
    secs = readers.scope_seconds(t)
    assert secs["other"] == pytest.approx(0.490180550999999)
    assert all(secs[s] == 0 for s in trace.SCOPES)
    assert readers.part_mfu(t, spec.cell("flux_img_mix").config, PEAK) == {}
    assert readers.host_stall_max_s(t) is None
    assert sorted({len(ops) for _, ops in readers.step_runs(t, 0)}) == [
        867, 1043]
    assert device_names_match(path, t)


def device_names_match(path, t) -> bool:
    """The metadata each op's path came from is the op's own: its display
    name is the HLO name its event name starts with.  XLA leaves a few
    ops with no ``tf_op`` (async copies and slices, a few fusions it made
    itself): they are ``other``, under a tenth of the step's time."""
    paths = trace.tf_op_paths(path.read_bytes())
    (plane,) = paths.values()
    for name, (display, tf_op) in plane.items():
        assert name.startswith(f"%{display} = "), (name, display)
        assert tf_op.startswith("jit(")
    step = [op for _, ops in readers.step_runs(t, 0) for op in ops]
    named = [(s, e, n) for s, e, n in step if n in plane]
    assert all(plane[n][1].startswith("jit(f)/") for _, _, n in named)
    share = sum(e - s for s, e, _ in named) / sum(e - s for s, e, _ in step)
    return share > 0.9


def test_trace_s_bounds_the_traced_batches(tmp_path):
    """The recorded run served two one-row batches, 2304 then 1024 latent
    tokens, four steps each.  Cut where a mix's ``trace_s`` would have
    stopped the profiler, after the first batch, the trace holds that
    batch's four steps, and only that batch is traced: the step MFU
    reads it alone.  Left unbounded, the batches outnumber the trace's
    steps and it reads nothing."""
    t = recorded(tmp_path, "flux_img_mix_1s.xplane.pb.gz")
    first_end = [m for m in t.devices[0].modules
                 if readers.STEP_MODULE.match(m[2])][3][1]
    t.devices[0].ops = [op for op in t.devices[0].ops if op[0] < first_end]
    t.devices[0].modules = [m for m in t.devices[0].modules
                            if m[0] < first_end]
    batches = [Batch(0.0, 0.3, 1, 2304), Batch(0.4, 0.5, 1, 1024)]
    cell = spec.cell("flux_img_mix")
    bounded = traced_batches(batches, 0.35)
    assert bounded == batches[:1]
    run = Run(cell, 1.0, 0.0, [], batches, bounded, t, "TPU v5 lite", 1, 0)
    mfu = readers.step_mfu(run)
    steps = [m for m in t.devices[0].modules
             if readers.STEP_MODULE.match(m[2])]
    assert len(steps) == 4
    assert mfu == pytest.approx(100 * 4 * flops.step_flops(
        cell.config, 1, 2304) / (sum(e - s for s, e, _ in steps) * 197e12))
    run.traced_batches = traced_batches(batches, 1.0)
    assert readers.step_mfu(run) is None
