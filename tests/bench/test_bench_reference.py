"""The float32 reference against the program at a tiny size, the seeded
weight recipe, the form lookup, and the FLOP count against XLA's."""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, reference, run, spec, weights
from repro.compat import make_mesh
from repro.configs import get_config
from repro.core import SPConfig
from repro.models import ParallelContext, get_model
from repro.models.dit import dit_forward
from repro.serving import SamplerConfig
from repro.serving.sampler import sample_step

SMALL = {"form": "dit_uniform",
         "model": {"base": "flux-12b", "d_model": 64, "n_heads": 2,
                   "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                   "n_layers": 2, "dtype": "float32"},
         "text_tokens": 256, "text_width": 64, "latent_channels": 64,
         "sampler": {"num_steps": 4}}
FORM = spec.form("dit_uniform")
DATA = pathlib.Path(__file__).parent / "data"


def program(config):
    m = dict(config["model"])
    cfg = dataclasses.replace(get_config(m.pop("base")), **m)
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = ParallelContext(mesh, SPConfig(strategy="full"), "prefill")
    struct = jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)[0])
    return cfg, ctx, struct


def test_reference_knows_the_programs_param_tree():
    cfg, _, struct = program(SMALL)
    n = FORM.Dims.of(SMALL)
    got = {weights.path_str(p): s.shape for p, s in
           jax.tree_util.tree_flatten_with_path(struct)[0]}
    want = dict(FORM.top_shapes(n))
    want.update({"layers/" + k: (n.layers,) + v
                 for k, v in FORM.block_shapes(n)})
    assert got == want


def test_whole_stack_and_single_layer_draws_agree():
    _, _, struct = program(SMALL)
    key = weights.base_key(2**40 + 9)
    params = jax.jit(lambda k: weights.make_params(struct, k, 2,
                                                   "bfloat16",
                                                   FORM.INIT))(key)
    n = FORM.Dims(64, 2, 32, 256, 2, 256, 64, 64, "bfloat16")
    one = reference.block_weights(key, FORM.block_shapes(n), n.layers,
                                  n.dtype, FORM.INIT, jnp.int32(1))
    for path, shape in FORM.block_shapes(n):
        a = params["layers"]
        for k in path.split("/"):
            a = a[k]
        np.testing.assert_array_equal(np.asarray(a[1], np.float32),
                                      np.asarray(one[path]))


def test_seeds_differing_above_32_bits_draw_different_weights():
    a = weights.base_key(5)
    b = weights.base_key(5 + 2**32)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))


@pytest.mark.parametrize("guidance", [1.0, 6.0])
def test_reference_sampler_matches_the_served_step(guidance):
    config = dict(SMALL, sampler={"num_steps": 4,
                                  "guidance_scale": guidance})
    cfg, ctx, struct = program(config)
    key = weights.base_key(3)
    params = jax.jit(lambda k: weights.make_params(struct, k, 2,
                                                   "float32",
                                                   FORM.INIT))(key)
    n = FORM.Dims.of(config)
    x0 = reference.initial_noise(17, 96, 64, "float32")
    cond = jax.random.normal(jax.random.PRNGKey(1), (256, 64))
    sc = SamplerConfig(num_steps=4, guidance_scale=guidance)
    x = x0[None]
    with jax.default_matmul_precision("highest"):
        for i in range(4):
            x = sample_step(params, cfg, ctx, x, cond[None],
                            jnp.float32(1.0 - i / 4), 0.25, sc)
        want = FORM.sample(key, n, x0, cond, 4, guidance)
        v_prog = dit_forward(params, cfg, ctx, latents=x0[None],
                             cond=cond[None], timesteps=jnp.ones((1,)))
        v_ref = reference.velocity(FORM, key, n, x0[None], cond[None], 1.0)
    np.testing.assert_allclose(np.asarray(v_prog), np.asarray(v_ref),
                               rtol=1e-4, atol=1e-4)
    # not vacuous: the sampler moves the latents a fair way
    assert float(jnp.linalg.norm(want - x0) / jnp.linalg.norm(x0)) > 0.05
    assert reference.rel_err(x[0], want, x0) < 1e-4


def test_fp8_control_departs_from_the_reference():
    key = weights.base_key(4)
    n = FORM.Dims.of(SMALL)
    x0 = reference.initial_noise(5, 64, 64, "float32")
    cond = jax.random.normal(jax.random.PRNGKey(2), (256, 64))
    with jax.default_matmul_precision("highest"):
        want = FORM.sample(key, n, x0, cond, 2)
        got = FORM.sample(key, n, x0, cond, 2, mode="fp8")
    assert reference.rel_err(got, want, x0) > 1e-2


@pytest.mark.parametrize("guidance", [1.0, 2.0])
def test_flop_count_matches_xla(guidance):
    config = {"form": "dit_uniform",
              "model": {"base": "flux-12b", "d_model": 256, "n_heads": 4,
                        "n_kv_heads": 4, "head_dim": 64, "d_ff": 1024,
                        "n_layers": 2, "dtype": "float32"},
              "text_tokens": 256, "text_width": 256, "latent_channels": 64,
              "sampler": {"num_steps": 4, "guidance_scale": guidance}}
    cfg, ctx, struct = program(config)
    sc = SamplerConfig(num_steps=4, guidance_scale=guidance)
    x = jax.ShapeDtypeStruct((2, 256, 64), jnp.float32)
    c = jax.ShapeDtypeStruct((2, 256, 256), jnp.float32)
    step = jax.jit(lambda p, x, c: sample_step(p, cfg, ctx, x, c,
                                               jnp.float32(1.0), 0.25, sc))
    xla = step.lower(struct, x, c).compile().cost_analysis()["flops"]
    ours = flops.step_flops(config, 2, 256)
    # XLA also counts the elementwise work that model FLOPs leave out
    assert 0.95 * xla < ours <= xla


def test_reference_split_over_chips_agrees_with_one_chip():
    """Block rows split over four devices and the guidance branches over
    two pairs give the one-device answer (four CPU devices, child
    process)."""
    code = (
        "import sys; sys.path[:0] = ['.', 'src']\n"
        "import jax, numpy as np\n"
        "from bench import reference, spec, weights\n"
        "f = spec.form('dit_uniform')\n"
        "n = f.Dims(64, 2, 32, 256, 2, 256, 64, 64, 'bfloat16')\n"
        "key = weights.base_key(8)\n"
        "x0 = reference.initial_noise(3, 124, 64, 'bfloat16')\n"
        "c = jax.random.normal(jax.random.PRNGKey(0), (256, 64))\n"
        "with jax.default_matmul_precision('highest'):\n"
        "    one = f.sample(key, n, x0, c, 2, 6.0)\n"
        "    four = f.sample(key, n, x0, c, 2, 6.0,"
        " devices=jax.devices()[:4])\n"
        "print(reference.rel_err(four, one, x0))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert float(res.stdout.strip().splitlines()[-1]) < 1e-5


def test_uniform_form_gives_the_latents_from_before_the_move():
    """``dit_uniform`` against latents that ``bench/reference.py`` computed
    before the architecture moved into its form module (float32 and the
    guided fp8 control at a tiny size), bit for bit.  A child process
    pinned to one CPU: XLA's CPU matmuls split their work by the threads
    they have, which moves the last bits."""
    code = (
        "import os, sys\n"
        "os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})\n"
        "sys.path[:0] = ['.', 'src']\n"
        "import jax, numpy as np\n"
        "from bench import reference, spec, weights\n"
        "f = spec.form('dit_uniform')\n"
        "n = f.Dims.of({'model': {'d_model': 64, 'n_heads': 2,"
        " 'head_dim': 32, 'd_ff': 128, 'n_layers': 2, 'dtype': 'bfloat16'},"
        " 'text_tokens': 256, 'text_width': 64, 'latent_channels': 64})\n"
        "key = weights.base_key(2**40 + 21)\n"
        "x0 = reference.initial_noise(12345, 48, 64, 'bfloat16')\n"
        "cond = jax.random.normal(jax.random.PRNGKey(7), (256, 64))\n"
        "with jax.default_matmul_precision('highest'):\n"
        "    a = f.sample(key, n, x0, cond, 2, 1.0)\n"
        "    b = f.sample(key, n, x0, cond, 2, 6.0, mode='fp8')\n"
        "g = np.load(sys.argv[1])\n"
        "print([bool(np.array_equal(g[k], v)) for k, v in"
        " (('x0', x0), ('f32', a), ('fp8_guided', b))])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", code,
         str(DATA / "dit_uniform_before_forms.npz")], cwd=spec.ROOT,
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip().splitlines()[-1] == "[True, True, True]"


def test_an_unknown_form_fails_with_its_name():
    with pytest.raises(ValueError, match="'dit_nonesuch'"):
        spec.form("dit_nonesuch")
    with pytest.raises(ValueError, match="dit_nonesuch"):
        run.model_config(dict(SMALL, form="dit_nonesuch"))


@pytest.mark.parametrize("where,key,value", [
    ("model", "act", "swiglu"), ("model", "norm", "rmsnorm"),
    ("model", "qkv_bias", True), ("model", "rope_theta", 500.0),
    ("top", "text_tokens", 226), ("top", "text_width", 4096),
    ("top", "latent_channels", 16)])
def test_a_config_off_the_forms_program_fails_with_its_name(where, key,
                                                             value):
    config = {**SMALL, "model": dict(SMALL["model"])}
    (config["model"] if where == "model" else config)[key] = value
    with pytest.raises(ValueError, match=f"form dit_uniform: {key}"):
        run.model_config(config)
    run.model_config(SMALL)  # the sizes the program takes pass


def test_text_and_latent_sizes_reach_the_reference_and_the_cond_pool():
    """The config's ``text_tokens``, ``text_width`` and
    ``latent_channels`` set the reference's weights, its input and output
    widths and the FLOP count, and the shape of the text embeddings the
    harness draws."""
    config = {**SMALL, "text_tokens": 10, "text_width": 48,
              "latent_channels": 16}
    n = FORM.Dims.of(config)
    assert (n.text_tokens, n.text_width, n.latent_channels) == (10, 48, 16)
    shapes = dict(FORM.top_shapes(n))
    assert shapes["cond_proj/w"] == (48, 64)
    assert shapes["proj_in/w"] == (16, 64)
    assert shapes["proj_out/w"] == (64, 16)
    x0 = reference.initial_noise(3, 24, 16, "float32")
    cond = jax.random.normal(jax.random.PRNGKey(0), (10, 48))
    assert FORM.sample(weights.base_key(1), n, x0, cond, 1).shape == (24, 16)
    pool = weights.cond_pool(weights.base_key(1), 10, 48, "bfloat16")
    assert pool.shape == (weights.COND_POOL, 10, 48)
    wider = FORM.forward_flops({**config, "text_width": 96}, 1, 24)
    assert wider - FORM.forward_flops(config, 1, 24) == 2 * 10 * 48 * 64
