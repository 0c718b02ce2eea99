"""The harness end to end on the CPU at a tiny size: a sound run is
correct and reports its metrics; without a TPU the command prints no
result and exits non-zero."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from bench import run, spec

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
