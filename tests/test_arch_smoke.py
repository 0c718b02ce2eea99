"""Per-architecture smoke tests (assignment deliverable (f)).

Each assigned arch instantiates a REDUCED variant of the same family
(≤2 layers, d_model ≤ 512, ≤4 experts) and runs one forward + one train
step on CPU, asserting output shapes and finiteness.  Full configs are
not instantiated here.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, get_config, get_reduced
from repro.configs.shapes import InputShape
from repro.core import SPConfig
from repro.models import ParallelContext, get_model
from repro.train import AdamWConfig, adamw_update, init_adamw

SP_FULL = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
SHAPE = InputShape("smoke", 32, 2, "training")


def _reduced_cfg(arch):
    cfg = get_reduced(arch)
    return dataclasses.replace(cfg, dtype="float32", sharding_overrides=())


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_constraints(arch):
    cfg = get_reduced(arch)
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    assert cfg.family == get_config(arch).family


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch, mesh1, rng):
    cfg = _reduced_cfg(arch)
    bundle = get_model(cfg)
    params, axes = bundle.init(cfg, rng, 1)
    batch = bundle.input_specs(cfg, SHAPE, abstract=False, key=rng,
                               dtype=jnp.float32)
    ctx = ParallelContext(mesh1, SP_FULL, "prefill")
    out = jax.jit(lambda p, b: bundle.apply(p, b, cfg, ctx))(params, batch)
    if cfg.family == "dit":
        assert out.shape == (SHAPE.global_batch, SHAPE.seq_len, 64)
    else:
        assert out.shape == (SHAPE.global_batch, SHAPE.seq_len, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch, mesh1, rng):
    cfg = _reduced_cfg(arch)
    bundle = get_model(cfg)
    params, axes = bundle.init(cfg, rng, 1)
    batch = bundle.input_specs(cfg, SHAPE, abstract=False, key=rng,
                               dtype=jnp.float32)
    ctx = ParallelContext(mesh1, SP_FULL, "train")
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = init_adamw(params, opt_cfg)

    @jax.jit
    def step(params, opt, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: bundle.loss(p, batch, cfg, ctx), has_aux=True)(params)
        params, opt, metrics = adamw_update(opt_cfg, grads, opt, params)
        return params, opt, loss, metrics

    params2, opt2, loss, metrics = step(params, opt, batch)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(metrics["grad_norm"]))
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x[0] - x[1]))),
        jax.tree.map(lambda a, b: (a, b), params, params2), 0.0)
    assert delta > 0.0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_assigned_config_matches_assignment(arch):
    """Full configs carry exactly the assigned numbers."""
    spec = {
        "qwen2-vl-2b": dict(n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2,
                            d_ff=8960, vocab=151936),
        "qwen2-moe-a2.7b": dict(n_layers=24, d_model=2048, n_heads=16,
                                n_kv_heads=16, vocab=151936),
        "stablelm-3b": dict(n_layers=32, d_model=2560, n_heads=32,
                            n_kv_heads=32, d_ff=6912, vocab=50304),
        "whisper-tiny": dict(n_layers=4, d_model=384, n_heads=6, n_kv_heads=6,
                             d_ff=1536, vocab=51865),
        "qwen2-1.5b": dict(n_layers=28, d_model=1536, n_heads=12, n_kv_heads=2,
                           d_ff=8960, vocab=151936),
        "hymba-1.5b": dict(n_layers=32, d_model=1600, n_heads=25, n_kv_heads=5,
                           d_ff=5504, vocab=32001),
        "arctic-480b": dict(n_layers=35, d_model=7168, n_heads=56,
                            n_kv_heads=8, d_ff=4864, vocab=32000),
        "rwkv6-1.6b": dict(n_layers=24, d_model=2048, n_heads=0, d_ff=7168,
                           vocab=65536),
        "chatglm3-6b": dict(n_layers=28, d_model=4096, n_heads=32,
                            n_kv_heads=2, d_ff=13696, vocab=65024),
        "starcoder2-7b": dict(n_layers=32, d_model=4608, n_heads=36,
                              n_kv_heads=4, d_ff=18432, vocab=49152),
    }[arch]
    cfg = get_config(arch)
    for k, v in spec.items():
        assert getattr(cfg, k) == v, (arch, k, getattr(cfg, k), v)
    moe_spec = {"qwen2-moe-a2.7b": (60, 4), "arctic-480b": (128, 2)}
    if arch in moe_spec:
        assert (cfg.moe.n_experts, cfg.moe.top_k) == moe_spec[arch]
    if arch == "rwkv6-1.6b":
        assert cfg.attention_free
    if arch == "hymba-1.5b":
        assert cfg.ssm is not None and cfg.ssm.state_size == 16
