"""The ``cogvideox_t2v_sp4`` cell's files: the cell loads, the program's
config is checked against the ``cogvideox`` form, the form's FLOP count
agrees with XLA's, and ``sp_comm_share.video``, ``proj_mfu.video`` and
``host_stall_max_s.video`` read hand-made traces."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import flops, readers, run, spec
from bench.run import Batch, Run
from bench.trace import Device, Trace
from repro.compat import make_mesh
from repro.configs import get_config
from repro.core import SPConfig
from repro.models import ParallelContext, get_model
from repro.serving import SamplerConfig
from repro.serving.sampler import sample_step

CELL = "cogvideox_t2v_sp4"
VIDEO = ("step_mfu.video", "attn_mfu.video", "mlp_mfu.video",
         "proj_mfu.video", "device_idle_share.video", "sp_comm_share.video",
         "host_stall_max_s.video")


def test_the_cell_loads_with_its_metrics():
    cell = spec.cell(CELL)
    assert cell.chips == 4
    assert set(cell.end_to_end) == {"latency_p50_s", "latency_p90_s",
                                    "setup_s"}
    assert set(cell.per_layer) == set(VIDEO)
    assert cell.traffic["loop"] == "backlog"
    assert cell.traffic["lengths"] == [13 * 30 * 45]
    assert (cell.config["text_tokens"] + 17550) % 4 == 0  # 4,444 a chip


def test_model_config_takes_the_cell_and_names_the_form_when_off_it():
    config = spec.cell(CELL).config
    cfg = run.model_config(config)
    assert (cfg.block, cfg.n_layers, cfg.n_heads, cfg.head_dim) == (
        "cogvideox", 21, 48, 64)
    off_model = {**config, "model": {**config["model"], "qk_norm": False}}
    with pytest.raises(ValueError, match="form cogvideox: qk_norm"):
        run.model_config(off_model)
    with pytest.raises(ValueError, match="form cogvideox: text_tokens"):
        run.model_config({**config, "text_tokens": 256})


@pytest.mark.parametrize("guidance", [1.0, 6.0])
def test_flop_count_matches_xla(guidance):
    model = {"base": "cogvideox-5b", "d_model": 256, "n_heads": 4,
             "n_kv_heads": 4, "head_dim": 64, "d_ff": 1024, "n_layers": 2,
             "dtype": "float32"}
    config = {"form": "cogvideox", "model": model, "text_tokens": 16,
              "text_width": 64, "latent_channels": 64,
              "sampler": {"num_steps": 4, "guidance_scale": guidance}}
    cfg = dataclasses.replace(
        get_config("cogvideox-5b"), text_tokens=16, text_width=64,
        patch_grid=(4, 8), **{k: v for k, v in model.items() if k != "base"})
    struct = jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)[0])
    ctx = ParallelContext(make_mesh((1, 1), ("data", "model")),
                          SPConfig(strategy="full"), "prefill")
    sc = SamplerConfig(num_steps=4, guidance_scale=guidance)
    x = jax.ShapeDtypeStruct((2, 256, 64), jnp.float32)
    c = jax.ShapeDtypeStruct((2, 16, 64), jnp.float32)
    step = jax.jit(lambda p, x, c: sample_step(p, cfg, ctx, x, c,
                                               jnp.float32(1.0), 0.25, sc))
    xla = step.lower(struct, x, c).compile().cost_analysis()["flops"]
    ours = flops.step_flops(config, 2, 256)
    # XLA also counts the elementwise work that model FLOPs leave out
    assert 0.95 * xla < ours <= xla


def _run(trace):
    return Run(spec.cell(CELL), 1.0, 0.0, [], [], [Batch(0, 1, 1, 17550)],
               trace, "TPU v5 lite", 4, 0)


def test_sp_comm_share_reads_a_hand_made_trace_and_nothing_without_one():
    """Two chips, each with one step execution: chip 0 spends 1 s of its
    4 s of step ops in ops under ``sp_comm``, chip 1 3 s of 6 s; an op
    outside the step (``jit_fold_in``) counts in neither."""
    read = spec.metric_reader("sp_comm_share.video")
    comm = "jit(f)/while/body/attn/shard_map/sp_comm/ppermute"
    paths = {"fusion.1": "jit(f)/while/body/mlp/dot_general",
             "collective-permute.2": comm, "all-to-all.3": comm,
             "fusion.9": "jit(fold_in)/threefry"}
    d0 = Device("/device:TPU:0",
                ops=[(0.0, 3.0, "fusion.1"), (3.0, 4.0, "all-to-all.3"),
                     (5.0, 6.0, "fusion.9")],
                modules=[(0.0, 4.0, "jit_f(1)"), (5.0, 6.0, "jit_fold_in")])
    d1 = Device("/device:TPU:1",
                ops=[(0.0, 3.0, "fusion.1"),
                     (3.0, 6.0, "collective-permute.2")],
                modules=[(0.0, 6.0, "jit_f(1)")])
    t = Trace([d0, d1], [], [], [paths, paths])
    assert read(_run(t)) == pytest.approx(100.0 * 4.0 / 10.0)
    # no op under the scope (a program without it, or SP=1): nothing
    plain = {k: v.replace("sp_comm/", "") for k, v in paths.items()}
    assert read(_run(Trace([d0, d1], [], [], [plain, plain]))) is None
    # a CPU run's trace has no chip; an untraced run has no trace
    assert read(_run(Trace([], [(0.0, 1.0, "bench.run_once")]))) is None
    assert read(_run(None)) is None
    for name in VIDEO:
        assert spec.metric_reader(name)(_run(None)) is None


def test_proj_mfu_leaves_the_qk_prep_ops_out():
    """One chip, one step execution after a dispatch of one 17,550-token
    video: 2 s of q/k/v matmuls, 1 s of ``qk_prep`` inside ``qkv`` and
    1 s of the output projection.  The projections' FLOPs are read over
    their 3 s, not over the 4 s that the ``qkv`` scope holds."""
    read = spec.metric_reader("proj_mfu.video")
    paths = {"fusion.1": "jit(f)/while/body/qkv/dot_general",
             "fusion.2": "jit(f)/while/body/qkv/qk_prep/mul",
             "fusion.3": "jit(f)/while/body/attn_out/dot_general"}
    d0 = Device("/device:TPU:0",
                ops=[(0.0, 2.0, "fusion.1"), (2.0, 3.0, "fusion.2"),
                     (3.0, 4.0, "fusion.3")],
                modules=[(0.0, 4.0, "jit_f(1)")])
    dispatch = (0.0, 0.01, readers.DISPATCH, {"rows": 1, "seq": 17550})
    t = Trace([d0], [], [dispatch], [paths])
    r = _run(t)
    work = flops.part_flops(r.cell.config, 1, 17550)["proj"]
    peak = r.peak["bf16_flops_per_s"]
    assert read(r) == pytest.approx(100.0 * work / (3.0 * peak))
    # the whole qkv scope is what the image cell's reader divides by
    assert r.part_mfu["proj"] == pytest.approx(100.0 * work / (4.0 * peak))
    # a program without the scopes: nothing
    bare = {k: "jit(f)/while/body/dot_general" for k in paths}
    assert read(_run(Trace([d0], [], [dispatch], [bare]))) is None


def test_host_stall_reads_the_longest_idle_stretch_in_run_once():
    """Chip 0 idles 0.5 s and then 1.5 s inside one ``engine.run_once``;
    the idle before the span does not count."""
    read = spec.metric_reader("host_stall_max_s.video")
    d0 = Device("/device:TPU:0",
                ops=[(0.0, 1.0, "fusion.1"), (2.5, 3.0, "fusion.1"),
                     (3.5, 4.0, "fusion.1")],
                modules=[(0.0, 4.0, "jit_f(1)")])
    t = Trace([d0], [], [(1.0, 4.0, readers.RUN_ONCE, {})], [{}])
    assert read(_run(t)) == pytest.approx(1.5)
