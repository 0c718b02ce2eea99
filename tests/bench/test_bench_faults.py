"""Faults planted under the timed path make ``correct`` come out false,
and so does the fp8 control put in the program's place, under the
listed cell's own limit (the harness on the CPU at a tiny size)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run, spec
from repro.serving import engine

ROOT = spec.ROOT


def go(cell, seed=11, seconds=2.0):
    return run.run_cell(cell, seed, seconds, False,
                        devices=jax.devices()[:1])


def unchanged(params, cfg, ctx, x_t, cond, t, dt, sc):
    return x_t


def half_batch(params, cfg, ctx, x_t, cond, t, dt, sc):
    h = x_t.shape[0] // 2
    done = REAL_STEP(params, cfg, ctx, x_t[:h], cond[:h], t, dt, sc)
    return jnp.concatenate([done, x_t[h:]], axis=0)


REAL_STEP = engine.sample_step


def altered(rid, latents, *a, **k):
    return REAL_RESULT(rid, latents.at[0, 0].add(1.0), *a, **k)


REAL_RESULT = engine.DiTResult


@pytest.mark.parametrize("name,fault", [
    ("sample_step", unchanged), ("sample_step", half_batch),
    ("DiTResult", altered)], ids=["step_unchanged", "half_batch",
                                  "answer_altered"])
def test_a_planted_fault_is_not_correct(tiny, monkeypatch, name, fault):
    monkeypatch.setattr(engine, name, fault)
    out = go(tiny())
    assert not out["correct"], out["checks"]


def test_without_an_exchange_between_chips_it_is_not_correct():
    """SP=4 with every chip attending only to its own quarter of the
    sequence, on four CPU devices in a child process."""
    code = (
        "import sys; sys.path[:0] = ['.', 'src', 'tests/bench']\n"
        "import jax, json\n"
        "from conftest import tiny_cell\n"
        "from bench import run\n"
        "from repro.core import strategy\n"
        "from repro.core.softmax import reference_attention\n"
        "cell = tiny_cell(4, sp={'strategy': 'swift_torus'},"
        " guidance=6.0)\n"
        "ok = run.run_cell(cell, 3, 2.0, False, devices=jax.devices()[:4])\n"
        "strategy.torus_attention = (lambda q, k, v, layout, **kw:"
        " reference_attention(q, k, v))\n"
        "bad = run.run_cell(cell, 3, 2.0, False, devices=jax.devices()[:4])\n"
        "print(json.dumps([ok['correct'], bad['correct']]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == [True, False]


def test_bf16_passes_where_the_fp8_control_fails(tiny):
    """The control at a size a test run holds, judged by the harness's own
    decision under ``flux_img_mix``'s limit: the program served in
    bfloat16 is correct, the fp8 reference in its place is not, and
    their readings lie three times apart or more."""
    limit = spec.cell("flux_img_mix").config["check"]["rel_err_limit"]
    cell = tiny(dtype="bfloat16", check_requests=2, limit=limit)
    prog = go(cell)
    ctrl = run.run_cell(cell, 11, 2.0, False, devices=jax.devices()[:1],
                        served="fp8")
    assert prog["correct"], prog["checks"]
    assert not ctrl["correct"], ctrl["checks"]
    assert (3 * prog["checks"]["rel_err_worst"]["value"]
            < ctrl["checks"]["rel_err_worst"]["value"])
