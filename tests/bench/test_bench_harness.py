"""The harness end to end on the CPU at a tiny size: a sound run is
correct and reports its metrics; without a TPU the command prints no
result and exits non-zero."""
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from bench import readers, run, spec

ROOT = spec.ROOT


def go(cell, seed=11, seconds=2.0, trace=False, devices=None):
    return run.run_cell(cell, seed, seconds, trace,
                        devices=devices or jax.devices()[:1])


def test_sound_run_is_correct_and_reports_its_metrics(tiny):
    out = go(tiny())
    assert out["correct"], out["checks"]
    assert out["checks"]["rel_err_worst"]["value"] < 1e-4
    assert set(out["metrics"]) == {"latency_p50_s", "latency_p90_s",
                                   "setup_s"}
    assert out["attempted"] == 8 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(tiny):
    out = go(tiny(), trace=True)
    assert out["correct"]
    assert "queue_wait_p90_s.image" in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("where", ["cpu", "bare_checkout"])
def test_command_exits_nonzero_with_no_result(tmp_path, where):
    cwd = ROOT
    if where == "bare_checkout":
        cwd = tmp_path / "co"
        cwd.mkdir()
        shutil.copy(ROOT / "BENCHMARK.json", cwd)
        for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
            shutil.copytree(ROOT / p, cwd / p,
                            ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cmd = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
    res = subprocess.run(cmd + ["--workload", "flux_img_mix", "--seed", "1",
                                "--seconds", "1", "--trace", "0"],
                         cwd=cwd, env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"correct"' not in res.stdout


def test_readers_find_nothing_without_a_trace(tiny):
    r = run.Run(tiny(), 1.0, 0.0, [], [], [], None, "TPU v5 lite", 1, 0)
    for name in ("step_mfu.image", "device_idle_share.image"):
        assert spec.metric_reader(name)(r) is None
    assert pathlib.Path(ROOT / "bench" / "metrics").is_dir()


def test_a_backlog_is_served_whole_and_trace_s_ends_the_trace_early(tiny):
    """A backlog mix's set is all submitted at the open and served to the
    last request, whatever ``seconds`` says; its ``trace_s`` ends the
    trace after the first batch, and only that batch counts as traced."""
    cell = tiny()
    cell = dataclasses.replace(cell, traffic={
        "loop": "backlog", "requests": 6, "lengths": [64, 128],
        "shares": [0.5, 0.5], "drain_s": 120, "trace_s": 1e-3})
    h = run.Harness(cell, 5, jax.devices()[:1])
    h.warm_up()
    marks = []
    reqs, batches = h.serve(
        0.5, lambda: marks.append(("close", h.now())),
        on_trace_end=lambda: marks.append(("trace", h.now())))
    assert len(reqs) == 6 and all(r.done is not None for r in reqs)
    assert all(r.due == 0.0 for r in reqs) and len(batches) >= 3
    assert [m[0] for m in marks] == ["trace", "close"]
    assert batches[0].end <= marks[0][1] < batches[1].start
    assert marks[1][1] >= batches[-1].end
    assert run.traced_batches(batches, marks[0][1]) == batches[:1]
    lat = readers.latencies(run.Run(cell, 0.5, 0.0, reqs, batches, [], None,
                                    "cpu", 1, 0))
    assert sorted(lat) == sorted(r.done for r in reqs)
