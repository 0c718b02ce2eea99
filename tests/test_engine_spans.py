"""The serving engine's spans and the DiT block's named scopes
(DESIGN.md §12): what a profile of ``DiTServer.run_once`` can name.

* ``run_once`` is one ``engine.run_once`` span holding ``engine.admit``,
  ``engine.prepare``, one ``engine.dispatch`` a sampler step,
  ``engine.sync`` and ``engine.finish``, in that order, tagged with the
  batch's rows and latent length and with no request or admission id,
  ``engine.dispatch`` also with the guidance ``branches`` a request runs;
* the default tracker's aggregates do not grow with the requests served;
* a slow sink's emission stays out of the measured step clock;
* each bucket build's ``plan_cache.trace`` span names its attention
  lowering (``attn``) and block kind (``block``);
* the step's HLO carries the block's scopes (``qkv``, ``attn``,
  ``attn_out``, ``mlp``; ``qk_prep``, ``modulate`` and, at SP > 1,
  ``sp_comm`` for CogVideoX's block) in its op names, which the device
  trace's ``tf_op`` paths come from.
"""
import dataclasses
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced
from repro.core import PipelineConfig, SPConfig, strategy
from repro.core.strategy import attention_lowering
from repro.models import ParallelContext, get_model
from repro.serving import DiTRequest, DiTServer, SamplerConfig
from repro.serving.metrics import RecordingTracker, Tracker
from repro.serving.sampler import sample_step
from tests.test_sampler import SlowTracker

ROOT = pathlib.Path(__file__).resolve().parents[1]
SP = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
SCOPES = ("qkv", "attn", "attn_out", "mlp")
STEPS = 3


@pytest.fixture(scope="module")
def dit():
    cfg = dataclasses.replace(get_reduced("flux-12b"), dtype="float32")
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)
    return cfg, params


class SpanSink(Tracker):
    """Keeps span records, and is not persistent: the engine's step loop
    stays the sync-free one."""

    def __init__(self):
        super().__init__()
        self.spans = []

    def _emit(self, rec):
        if rec.kind == "span":
            self.spans.append(rec)


@pytest.mark.parametrize("measured", [False, True])
def test_run_once_spans_nest_in_order(dit, mesh1, measured):
    cfg, params = dit
    sink = RecordingTracker() if measured else SpanSink()
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=STEPS), max_batch=2,
                    tracker=sink)
    for rid in (11, 12):
        srv.submit(DiTRequest(rid=rid, seq_len=32))
    assert len(srv.run_once()) == 2
    spans = [r for r in (sink.records if measured else sink.spans)
             if r.kind == "span" and r.name.startswith("engine.")]
    outer = spans[-1]
    assert outer.name == "engine.run_once" and outer.tags == {}
    inner = sorted(spans[:-1], key=lambda r: r.t_start)
    # measured: a sync after each step; else one after the last
    steps = (["engine.dispatch", "engine.sync"] * STEPS if measured
             else ["engine.dispatch"] * STEPS + ["engine.sync"])
    assert [r.name for r in inner] == [
        "engine.admit", "engine.prepare", *steps, "engine.finish"]
    assert [r.step for r in inner if r.name == "engine.dispatch"] == list(
        range(STEPS))
    end = outer.t_start + outer.value
    for r in inner:
        assert r.tags["parent"] == "engine.run_once"
        assert outer.t_start <= r.t_start
        assert r.t_start + r.value <= end + 1e-9
        if r.name == "engine.dispatch":  # one forward a request: unguided
            assert r.tags == {"rows": 2, "seq": 32, "branches": 1,
                              "parent": "engine.run_once"}
        elif r.name != "engine.admit":
            assert r.tags == {"rows": 2, "seq": 32,
                              "parent": "engine.run_once"}
    for a, b in zip(inner, inner[1:]):  # one after another, no overlap
        assert a.t_start + a.value <= b.t_start + 1e-9


def test_slow_tracker_does_not_inflate_engine_step_clock(dit, mesh1):
    """A persistent sink measures every step; the step's dispatch and sync
    records are written after its clock stops, so ``engine.t_step_s``
    (what the calibrator and the preemption policy read) holds no sink
    time.  Step 0 pays the compile, so the steady steps are checked."""
    cfg, params = dit
    sink = SlowTracker()
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=STEPS), max_batch=2,
                    tracker=sink)
    srv.submit(DiTRequest(rid=1, seq_len=32))
    (res,) = srv.run_once()
    names = [r.name for r in sink.records]
    assert names.count("engine.dispatch") == STEPS
    assert names.count("engine.sync") == STEPS
    assert len(res.step_times) == STEPS
    steady = [r.value for r in sink.records
              if r.name == "engine.t_step_s" and r.step > 0]
    assert steady == res.step_times[1:]
    for t_step in steady:
        assert t_step < SlowTracker.EMIT_S, (
            f"t_step_s {t_step:.3f}s includes sink emission time")


@pytest.mark.parametrize("pipeline", [None, PipelineConfig(pp=2,
                                                          warmup_steps=1)],
                         ids=["sync", "pipelined"])
def test_plan_cache_trace_tags_the_attention_lowering(dit, mesh1, pipeline):
    """Each bucket build's ``plan_cache.trace`` span names the attention
    its steps run: the predicate ``sp_attention`` uses (the oracle on the
    CPU), or displaced attention in a pipelined bucket."""
    cfg, params = dit
    sink = RecordingTracker()
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=2, pipeline=pipeline),
                    max_batch=2, tracker=sink)
    for rid, seq in ((1, 32), (2, 64)):
        srv.submit(DiTRequest(rid=rid, seq_len=seq))
    assert len(srv.serve()) == 2
    builds = [r for r in sink.records if r.name == "plan_cache.trace"]
    assert sorted(r.tags["seq"] for r in builds) == [32, 64]
    for r in builds:
        want = "displaced" if pipeline else attention_lowering(
            SP, mesh1, cfg.text_tokens + r.tags["seq"], cfg.resolved_head_dim)
        assert r.tags["attn"] == want
        assert want in ("reference", "displaced")
        assert r.tags["block"] == "uniform"


def test_plan_cache_trace_tags_the_one_chip_flash_lowering(mesh1,
                                                           monkeypatch):
    """Where the lowering's platform test reports a TPU (the kernel still
    runs interpreted here), a one-chip SP=1 bucket of 64-wide heads has
    the ``attn`` tag ``flash``."""
    monkeypatch.setattr(strategy, "pallas_interpret", lambda: False)
    cfg = dataclasses.replace(get_reduced("flux-12b"), dtype="float32",
                              n_heads=2, n_kv_heads=2, head_dim=64)
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)
    sink = RecordingTracker()
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=1), max_batch=1,
                    tracker=sink)
    srv.submit(DiTRequest(rid=1, seq_len=32))
    assert len(srv.serve()) == 1
    (build,) = [r for r in sink.records if r.name == "plan_cache.trace"]
    assert build.tags["attn"] == "flash"


def test_sp4_bucket_tags_its_stage_lowering():
    """An SP > 1 bucket's ``plan_cache.trace`` names the strategy and the
    attend inside its stages: ``swift_torus+reference`` on the CPU, and
    ``swift_torus+flash`` where the platform test reports a TPU (the
    kernel then runs interpreted).  Four CPU devices, a child process;
    two heads of 64 at SP=4 plan two Ulysses pairs that ring the KV."""
    code = (
        "import sys; sys.path[:0] = ['src']\n"
        "import dataclasses, json\n"
        "import jax\n"
        "from repro.compat import make_mesh\n"
        "from repro.configs import get_reduced\n"
        "from repro.core import SPConfig, strategy\n"
        "from repro.models import get_model\n"
        "from repro.serving import DiTRequest, DiTServer, SamplerConfig\n"
        "from repro.serving.metrics import RecordingTracker\n"
        "cfg = dataclasses.replace(get_reduced('flux-12b'), dtype='float32',"
        " n_heads=2, n_kv_heads=2, head_dim=64)\n"
        "params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)\n"
        "mesh = make_mesh((1, 4), ('data', 'model'))\n"
        "sp = SPConfig(strategy='swift_torus', sp_axes=('model',),"
        " batch_axes=('data',))\n"
        "tags = []\n"
        "for tpu in (False, True):\n"
        "    if tpu:\n"
        "        strategy.pallas_interpret = lambda: False\n"
        "    sink = RecordingTracker()\n"
        "    srv = DiTServer(params, cfg, mesh, sp, max_batch=1,"
        " sampler=SamplerConfig(num_steps=1), tracker=sink)\n"
        "    srv.submit(DiTRequest(rid=1, seq_len=32))\n"
        "    assert len(srv.serve()) == 1\n"
        "    tags += [r.tags['attn'] for r in sink.records"
        " if r.name == 'plan_cache.trace']\n"
        "print(json.dumps(tags))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == [
        "swift_torus+reference", "swift_torus+flash"]


def test_cogvideox_spans_tag_the_block_and_the_guidance_branches(mesh1):
    """A guided ``cogvideox`` bucket: its build's ``plan_cache.trace``
    names the block kind, and each ``engine.dispatch`` says that a step
    runs two forwards (branches) of each request."""
    cfg = dataclasses.replace(get_reduced("cogvideox-5b"), dtype="float32")
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)
    sink = RecordingTracker()
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=2, guidance_scale=6.0),
                    max_batch=1, tracker=sink)
    srv.submit(DiTRequest(rid=3, seq_len=32))
    assert len(srv.serve()) == 1
    (build,) = [r for r in sink.records if r.name == "plan_cache.trace"]
    assert build.tags["block"] == "cogvideox"
    disp = [r for r in sink.records if r.name == "engine.dispatch"]
    assert len(disp) == 2 and all(r.tags["branches"] == 2 for r in disp)


def served_series(dit, mesh, requests: int) -> dict:
    """The default tracker's series (name -> tag sets) after serving
    ``requests`` requests of one length in full batches of two."""
    cfg, params = dit
    srv = DiTServer(params, cfg, mesh, SP,
                    sampler=SamplerConfig(num_steps=2), max_batch=2)
    for rid in range(requests):
        srv.submit(DiTRequest(rid=rid, seq_len=32))
    assert len(srv.serve()) == requests
    done = srv.tracker.series("engine.request_done",
                              {"preemptions": 0, "sla_met": True, "seq": 32})
    assert done.n == requests
    out: dict = {}
    for row in srv.tracker.summary():
        out.setdefault(row["name"], []).append(row["tags"])
    return out


def test_default_tracker_series_do_not_grow_with_requests(dit, mesh1):
    """Serving 4 or 10 requests (2 or 5 batches) leaves the default
    tracker with the same series: request and admission ids split none."""
    few = served_series(dit, mesh1, 4)
    assert few == served_series(dit, mesh1, 10)
    assert few["engine.request_done"] == [
        {"preemptions": 0, "seq": 32, "sla_met": True}]
    assert few["engine.batch_done"] == [{"rows": 2, "seq": 32}]


def test_block_scopes_name_the_step_ops(dit, mesh1):
    cfg, params = dit
    ctx = ParallelContext(mesh1, SP, "prefill")
    sc = SamplerConfig(num_steps=2)

    def f(params, x, cond, t):
        return sample_step(params, cfg, ctx, x, cond, t, 0.5, sc)

    lowered = jax.jit(f).lower(params, jnp.zeros((1, 32, 64)),
                               jnp.zeros((1, 256, cfg.d_model)),
                               jnp.float32(1.0))
    text = lowered.as_text(debug_info=True)
    op_names = set(re.findall(r'op_name="([^"]*)"',
                              lowered.compile().as_text()))
    for scope in SCOPES:
        assert f'"{scope}/' in text, scope
        assert any(f"/{scope}/" in n for n in op_names), scope
    # the attention core's score and softmax work is under ``attn``
    attn = {n.split("/attn/", 1)[1] for n in op_names if "/attn/" in n}
    assert {"reduce_max", "exp"} <= {a.split("/")[-1] for a in attn}
    assert any(n.startswith("bhlk,bkhd->blhd") for n in attn)  # p @ v


def test_cogvideox_scopes_name_the_sp4_step_ops():
    """At SP=4 (``swift_torus`` on four CPU devices, a child process) the
    compiled guided step of the ``cogvideox`` block carries ``qk_prep``
    (inside ``qkv``), ``modulate`` and ``sp_comm`` (inside ``attn``: the
    torus permutes and the Push-O all-to-all) in its ops' names, which
    the device trace's ``tf_op`` paths come from."""
    code = (
        "import sys; sys.path[:0] = ['src']\n"
        "import dataclasses, json, re\n"
        "import jax, jax.numpy as jnp\n"
        "from repro.compat import make_mesh\n"
        "from repro.configs import get_reduced\n"
        "from repro.core import SPConfig\n"
        "from repro.models import ParallelContext, get_model\n"
        "from repro.serving import SamplerConfig\n"
        "from repro.serving.sampler import sample_step\n"
        "cfg = dataclasses.replace(get_reduced('cogvideox-5b'),"
        " dtype='float32')\n"
        "p = jax.eval_shape(lambda: get_model(cfg).init(cfg,"
        " jax.random.PRNGKey(0), 1)[0])\n"
        "ctx = ParallelContext(make_mesh((1, 4), ('data', 'model')),"
        " SPConfig(strategy='swift_torus', sp_axes=('model',),"
        " batch_axes=('data',)), 'prefill')\n"
        "sc = SamplerConfig(num_steps=2, guidance_scale=6.0)\n"
        "f = jax.jit(lambda p, x, c: sample_step(p, cfg, ctx, x, c,"
        " jnp.float32(1.0), 0.5, sc))\n"
        "txt = f.lower(p, jax.ShapeDtypeStruct((1, 48, 64), jnp.float32),"
        " jax.ShapeDtypeStruct((1, cfg.text_tokens, cfg.cond_width),"
        " jnp.float32)).compile().as_text()\n"
        "ops = re.findall(r' (collective-permute|all-to-all)\\(.*?"
        "op_name=\"([^\"]*)\"', txt)\n"
        "print(json.dumps({'names': sorted(set(re.findall("
        "r'op_name=\"([^\"]*)\"', txt))), 'comm': ops}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    names = out["names"]
    assert any("/qkv/qk_prep/" in n for n in names)
    assert any("/modulate/" in n for n in names)
    assert any("/attn/" in n and "/sp_comm/" in n for n in names)
    in_scope = {kind for kind, name in out["comm"] if "/sp_comm/" in name}
    assert in_scope == {"collective-permute", "all-to-all"}
