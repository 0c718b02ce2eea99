"""CogVideoX's block served at SP=4 (``swift_torus`` on four of the fake
devices) through ``DiTServer`` against the plain float32 form
``bench/forms/cogvideox.py``, with guidance.  Both in float32 at
``highest`` precision: they agree to about 1e-6 of the latents' motion,
and 1e-5 fails on a lost exchange, mask or rotation."""
import dataclasses
import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import jax  # noqa: E402

from bench import reference, spec, weights  # noqa: E402
from repro.compat import make_mesh  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import SPConfig  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serving import DiTRequest, DiTServer, SamplerConfig  # noqa: E402


def test_sp4_swift_torus_matches_the_form():
    form = spec.form("cogvideox")
    # 12 text + 2 frames of 4 x 6 = 60 joint tokens, 15 a chip
    cfg = dataclasses.replace(
        get_config("cogvideox-5b"), n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=4, head_dim=32, d_ff=512, text_tokens=12, text_width=48,
        time_embed_dim=64, patch_grid=(4, 6), rope_axes=(8, 12, 12),
        dtype="float32")
    n = form.Dims(128, 4, 32, 512, 2, 12, 48, 64, "float32", 64, (4, 6),
                  (8, 12, 12))
    devices = jax.devices()[:4]
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices)
    sp = SPConfig(strategy="swift_torus", sp_axes=("model",),
                  batch_axes=("data",))
    struct = jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)[0])
    key = weights.base_key(2**40 + 5)
    params = jax.jit(lambda k: weights.make_params(
        struct, k, 2, "float32", form.INIT))(key)
    cond = jax.random.normal(jax.random.PRNGKey(1), (12, 48))
    srv = DiTServer(params, cfg, mesh, sp,
                    sampler=SamplerConfig(num_steps=3, guidance_scale=6.0),
                    max_batch=1)
    srv.submit(DiTRequest(rid=21, seq_len=48, cond=cond))
    with jax.default_matmul_precision("highest"):
        (res,) = srv.serve()
        x0 = reference.initial_noise(21, 48, 64, "float32")
        want = form.sample(key, n, x0, cond, 3, 6.0, devices=devices)
    assert reference.rel_err(res.latents, want, x0) < 1e-5
