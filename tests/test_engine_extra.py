"""Extra serving-engine coverage: batch padding, mixed lengths, DiT batch
divisibility, sampler step math."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core import SPConfig
from repro.models import ParallelContext, get_model
from repro.models.dit import dit_forward
from repro.serving import DiTRequest, DiTServer, SamplerConfig
from repro.serving.sampler import sample_step

SP = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))


@pytest.fixture(scope="module")
def dit():
    cfg = dataclasses.replace(get_reduced("cogvideox-5b"), dtype="float32")
    bundle = get_model(cfg)
    params, _ = bundle.init(cfg, jax.random.PRNGKey(0), 1)
    return cfg, params


def test_single_request_batch_pads(dit, mesh1):
    cfg, params = dit
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=1), max_batch=4)
    srv.submit(DiTRequest(rid=0, seq_len=32))
    out = srv.serve()
    assert len(out) == 1 and out[0].latents.shape == (32, 64)


def test_euler_step_direction(dit, mesh1):
    """x_{t-dt} = x_t - dt*v: a zero-velocity model leaves x unchanged."""
    cfg, params = dit
    # zero the output projection -> v == 0 (proj_out is zero-init already,
    # but adaLN gates are zero-init too; assert the identity holds)
    ctx = ParallelContext(mesh1, SP, "prefill")
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    cond = jnp.zeros((1, cfg.text_tokens, cfg.cond_width))
    v = dit_forward(params, cfg, ctx, latents=x, cond=cond,
                    timesteps=jnp.ones((1,)))
    x2 = sample_step(params, cfg, ctx, x, cond, jnp.float32(1.0),
                     jnp.float32(0.5), SamplerConfig(num_steps=2))
    np.testing.assert_allclose(np.asarray(x2), np.asarray(x - 0.5 * v),
                               rtol=1e-5, atol=1e-6)


def test_guidance_scale_path(dit, mesh1):
    cfg, params = dit
    ctx = ParallelContext(mesh1, SP, "prefill")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 64))
    cond = jax.random.normal(jax.random.PRNGKey(3),
                             (1, cfg.text_tokens, cfg.cond_width)) * 0.02
    out = sample_step(params, cfg, ctx, x, cond, jnp.float32(1.0),
                      jnp.float32(0.25), SamplerConfig(num_steps=4,
                                                       guidance_scale=3.0))
    assert out.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(out)))
