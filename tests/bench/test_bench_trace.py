"""Trace reduction: interval arithmetic on a hand-made trace, and the
whole reduction on a small trace recorded on a TPU v5e."""
import gzip
import pathlib

import pytest

from bench import readers, spec, trace
from bench.run import Batch, Run
from bench.trace import Device, Trace

DATA = pathlib.Path(__file__).parent / "data"


def hand_made() -> Trace:
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
    t = hand_made()
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
