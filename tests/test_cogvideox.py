"""CogVideoX's block (``ModelConfig.block == "cogvideox"``) against the
plain float32 form ``bench/forms/cogvideox.py`` at a small size on seeded
weights, its parts one by one, and the uniform block left as it was.

Tolerances: program and form both compute in float32 at ``highest``
matmul precision and differ only in the order of their operations, so
they agree to about 1e-6 of the latents' motion; 1e-5 leaves room for
that and fails on any departure of the maths (a lost bias, norm, gate or
rotation moves the result by 1e-2 or more).
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from repro.compat import make_mesh
from repro.configs import get_config, get_reduced
from repro.core import SPConfig
from repro.models import ParallelContext, get_model
from repro.models.blocks import apply_rope3d
from repro.models.dit import _expert_modulate, _time_embedding, dit_forward
from repro.serving import DiTRequest, DiTServer, SamplerConfig
from repro.serving.sampler import sample_step

ROOT = pathlib.Path(__file__).resolve().parents[1]
FORM = spec.form("cogvideox")
# d 128, 4 x 32 heads, 2 frames of 4 x 6 patches, 10 text tokens 48 wide
CFG = dataclasses.replace(
    get_config("cogvideox-5b"), n_layers=2, d_model=128, n_heads=4,
    n_kv_heads=4, head_dim=32, d_ff=512, text_tokens=10, text_width=48,
    time_embed_dim=64, patch_grid=(4, 6), rope_axes=(8, 12, 12),
    dtype="float32")
DIMS = FORM.Dims(128, 4, 32, 512, 2, 10, 48, 64, "float32", 64, (4, 6),
                 (8, 12, 12))
LATENT = 48
TOL = 1e-5


@pytest.fixture(scope="module")
def seeded():
    struct = jax.eval_shape(
        lambda: get_model(CFG).init(CFG, jax.random.PRNGKey(0), 1)[0])
    key = weights.base_key(2**40 + 3)
    params = jax.jit(lambda k: weights.make_params(
        struct, k, CFG.n_layers, "float32", FORM.INIT))(key)
    return key, struct, params


def ctx1():
    return ParallelContext(make_mesh((1, 1), ("data", "model")),
                           SPConfig(strategy="full"), "prefill")


def test_the_form_knows_the_programs_param_tree(seeded):
    _, struct, _ = seeded
    got = {weights.path_str(p): s.shape for p, s in
           jax.tree_util.tree_flatten_with_path(struct)[0]}
    want = dict(FORM.top_shapes(DIMS))
    want.update({"layers/" + k: (DIMS.layers,) + v
                 for k, v in FORM.block_shapes(DIMS)})
    assert got == want


def test_published_widths_and_parameter_count():
    """48 x 64 heads at width 3072, d_ff 12288, 226 x 4096 text, a 512-wide
    timestep embedding; 42 blocks hold 5.57B parameters, within 3% of the
    published 5.6B."""
    cfg = get_config("cogvideox-5b")
    assert (cfg.n_heads, cfg.head_dim, cfg.d_model, cfg.d_ff) == (
        48, 64, 3072, 12288)
    assert (cfg.text_tokens, cfg.cond_width, cfg.time_embed_dim) == (
        226, 4096, 512)
    for k, v in FORM.PROGRAM_KEYS.items():
        assert getattr(cfg, k) == v, k
    struct = jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)[0])
    n = sum(a.size for a in jax.tree.leaves(struct))
    assert abs(n - 5.6e9) / 5.6e9 < 0.03, n


@pytest.mark.parametrize("guidance", [1.0, 6.0])
def test_sampler_steps_match_the_form(seeded, guidance):
    key, _, params = seeded
    x0 = reference.initial_noise(17, LATENT, 64, "float32")
    cond = jax.random.normal(jax.random.PRNGKey(1), (10, 48))
    sc = SamplerConfig(num_steps=4, guidance_scale=guidance)
    x = x0[None]
    with jax.default_matmul_precision("highest"):
        v_prog = dit_forward(params, CFG, ctx1(), latents=x0[None],
                             cond=cond[None], timesteps=jnp.full((1,), 0.7))
        v_ref = reference.velocity(FORM, key, DIMS, x0[None], cond[None],
                                   0.7)
        for i in range(4):
            x = sample_step(params, CFG, ctx1(), x, cond[None],
                            jnp.float32(1.0 - i / 4), 0.25, sc)
        want = FORM.sample(key, DIMS, x0, cond, 4, guidance)
    np.testing.assert_allclose(np.asarray(v_prog), np.asarray(v_ref),
                               rtol=TOL, atol=TOL)
    # not vacuous: the sampler moves the latents a fair way
    assert float(jnp.linalg.norm(want - x0) / jnp.linalg.norm(x0)) > 0.05
    assert reference.rel_err(x[0], want, x0) < TOL


def test_rope3d_matches_a_per_axis_formula():
    """Each video token's pair i of its axis slice turns by coord *
    10000^(-2i / slice width) about (x[2i], x[2i+1]); text is untouched."""
    rng = np.random.default_rng(0)
    text, frames, (rows, cols) = CFG.text_tokens, 2, CFG.patch_grid
    length = text + frames * rows * cols
    x = rng.standard_normal((1, length, 2, CFG.head_dim)).astype(np.float32)
    got = np.asarray(apply_rope3d(jnp.asarray(x),
                                  jnp.arange(length)[None], CFG))
    want = x.copy()
    for p in range(text, length):
        lat = p - text
        coords = (lat // (rows * cols), lat // cols % rows, lat % cols)
        off = 0
        for coord, width in zip(coords, CFG.rope_axes):
            for i in range(width // 2):
                a = coord * 10000.0 ** (-2 * i / width)
                j = off + 2 * i
                x0, x1 = x[0, p, :, j], x[0, p, :, j + 1]
                want[0, p, :, j] = x0 * np.cos(a) - x1 * np.sin(a)
                want[0, p, :, j + 1] = x1 * np.cos(a) + x0 * np.sin(a)
            off += width
    np.testing.assert_array_equal(got[:, :text], x[:, :text])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # and as the form builds it, from diffusers' tables
    cos, sin = FORM.rotary_tables(DIMS, length - text)
    np.testing.assert_allclose(
        got, np.asarray(FORM.apply_rotary(jnp.asarray(x), cos, sin)),
        rtol=1e-5, atol=1e-5)


def test_time_features_differ_across_t(seeded):
    """Real sinusoidal features reach the embedding: two timesteps give
    different embeddings and velocities, each the form's."""
    key, _, params = seeded
    ts = jnp.asarray([0.25, 0.75])
    emb = _time_embedding(params, CFG, ts, jnp.float32)
    assert float(jnp.abs(emb[0] - emb[1]).max()) > 1e-2
    top = reference.top_weights(key, FORM.top_shapes(DIMS), 2, "float32",
                                FORM.INIT)
    x0 = reference.initial_noise(3, LATENT, 64, "float32")[None]
    cond = jnp.zeros((1, 10, 48))
    with jax.default_matmul_precision("highest"):
        for i, t in enumerate((0.25, 0.75)):
            _, want = FORM.embed(top, x0, cond, jnp.float32(t), "f32")
            np.testing.assert_allclose(np.asarray(emb[i]),
                                       np.asarray(want[0]), rtol=TOL,
                                       atol=TOL)
        v = [dit_forward(params, CFG, ctx1(), latents=x0, cond=cond,
                         timesteps=jnp.full((1,), t)) for t in (0.25, 0.75)]
    assert float(jnp.abs(v[0] - v[1]).max()) > 1e-2


def test_text_and_video_take_different_modulations():
    """Rows before ``text_tokens`` take the text's (shift, scale, gate),
    the rest the video's."""
    d, text, length = 4, 3, 7
    mod = jnp.arange(6 * d, dtype=jnp.float32)[None] / 10.0  # [1, 6d]
    h = jnp.ones((1, length, d))
    is_text = (jnp.arange(length) < text)[None, :, None]
    out, gate = _expert_modulate(h, mod, is_text)
    sh, sc, g, tsh, tsc, tg = np.split(np.asarray(mod[0]), 6)
    np.testing.assert_allclose(out[0, :text], np.broadcast_to(
        1 + tsc + tsh, (text, d)), rtol=1e-6)
    np.testing.assert_allclose(out[0, text:], np.broadcast_to(
        1 + sc + sh, (length - text, d)), rtol=1e-6)
    np.testing.assert_array_equal(gate[0, :text], np.broadcast_to(
        tg, (text, d)))
    np.testing.assert_array_equal(gate[0, text:], np.broadcast_to(
        g, (length - text, d)))


def test_served_path_runs_the_block_and_refuses_a_partial_frame(seeded):
    """DiTServer serves the block through the scheduler and plan cache;
    a latent length that is not whole frames of the patch grid fails with
    the grid named."""
    _, _, params = seeded
    mesh = make_mesh((1, 1), ("data", "model"))
    sp = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
    srv = DiTServer(params, CFG, mesh, sp,
                    sampler=SamplerConfig(num_steps=2, guidance_scale=6.0),
                    max_batch=2)
    srv.submit(DiTRequest(rid=1, seq_len=LATENT))
    (res,) = srv.serve()
    assert res.latents.shape == (LATENT, 64)
    assert bool(jnp.all(jnp.isfinite(res.latents)))
    srv.submit(DiTRequest(rid=2, seq_len=LATENT + 1))
    with pytest.raises(ValueError, match=r"\(4, 6\)"):
        srv.serve()


def test_bf16_block_carries_a_float32_residual_stream():
    """Served in bfloat16, the blocks' scan carries the joint rows in
    float32 and the velocity comes out in bfloat16."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16", n_layers=3)
    params = jax.eval_shape(
        lambda: get_model(cfg).init(cfg, jax.random.PRNGKey(0), 1)[0])
    jaxpr = jax.make_jaxpr(lambda p, x, c, t: dit_forward(
        p, cfg, ctx1(), latents=x, cond=c, timesteps=t))(
        params, jax.ShapeDtypeStruct((1, LATENT, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 10, 48), jnp.bfloat16),
        jax.ShapeDtypeStruct((1,), jnp.float32))
    (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    carry = scan.outvars[0].aval
    assert carry.shape == (1, 10 + LATENT, 128)
    assert carry.dtype == jnp.float32
    assert jaxpr.out_avals[0].dtype == jnp.bfloat16


def test_displaced_pipelining_refuses_the_block(seeded):
    from repro.core.pipefusion import KVState
    from repro.models.dit import dit_forward_displaced

    _, _, params = seeded
    kv = KVState(jnp.zeros((2, 1, 58, 4, 32)), jnp.zeros((2, 1, 58, 4, 32)))
    with pytest.raises(ValueError, match="uniform block only"):
        dit_forward_displaced(params, CFG, ctx1(),
                              latents=jnp.zeros((1, LATENT, 64)),
                              cond=jnp.zeros((1, 10, 48)),
                              timesteps=jnp.ones((1,)), kv_state=kv,
                              num_patches=2)


def test_reduced_preset_is_the_same_block_at_toy_widths():
    small = get_reduced("cogvideox-5b")
    full = get_config("cogvideox-5b")
    for k in ("block", "rope", "qk_norm", "proj_bias", "qkv_bias", "act",
              "norm"):
        assert getattr(small, k) == getattr(full, k), k
    assert sum(small.rope_axes) == small.head_dim


def test_bf16_passes_where_the_fp8_control_fails(seeded):
    """The program served in bfloat16 through ``DiTServer`` against the
    float32 form, and the form with float8 matmul operands in its place,
    judged under ``cogvideox_t2v_sp4``'s own limit: the program passes,
    the control fails, and their readings lie three times apart or more."""
    key, struct, _ = seeded
    limit = spec.cell("cogvideox_t2v_sp4").config["check"]["rel_err_limit"]
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = jax.jit(lambda k: weights.make_params(
        struct, k, cfg.n_layers, "bfloat16", FORM.INIT))(key)
    n = dataclasses.replace(DIMS, dtype="bfloat16")
    cond = weights.cond_pool(key, 10, 48, "bfloat16")[0]
    srv = DiTServer(params, cfg, make_mesh((1, 1), ("data", "model")),
                    SPConfig(strategy="full", sp_axes=("model",),
                             batch_axes=("data",)),
                    sampler=SamplerConfig(num_steps=4, guidance_scale=6.0),
                    max_batch=1)
    srv.submit(DiTRequest(rid=9, seq_len=LATENT, cond=cond))
    (res,) = srv.serve()
    x0 = reference.initial_noise(9, LATENT, 64, "bfloat16")
    c32 = jnp.asarray(cond, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = FORM.sample(key, n, x0, c32, 4, 6.0)
        fp8 = FORM.sample(key, n, x0, c32, 4, 6.0, mode="fp8")
    prog = reference.rel_err(np.asarray(res.latents, np.float32), want, x0)
    ctrl = reference.rel_err(fp8, want, x0)
    assert prog <= limit < ctrl, (prog, ctrl, limit)
    assert 3 * prog < ctrl


def test_uniform_block_is_bit_identical_to_before_the_block_kinds():
    """The uniform block's velocity and one guided sampler step, float32
    on seeded weights, bit for bit against arrays the code before the
    ``cogvideox`` kind computed.  A child process pinned to one CPU:
    XLA's CPU matmuls split their work by the threads they have, which
    moves the last bits."""
    code = (
        "import os, sys\n"
        "os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})\n"
        "sys.path[:0] = ['.', 'src']\n"
        "import dataclasses\n"
        "import jax, jax.numpy as jnp, numpy as np\n"
        "from bench import weights\n"
        "from repro.compat import make_mesh\n"
        "from repro.configs import get_reduced\n"
        "from repro.core import SPConfig\n"
        "from repro.models import ParallelContext, get_model\n"
        "from repro.models.dit import dit_forward\n"
        "from repro.serving import SamplerConfig\n"
        "from repro.serving.sampler import sample_step\n"
        "cfg = dataclasses.replace(get_reduced('flux-12b'), dtype='float32')\n"
        "ctx = ParallelContext(make_mesh((1, 1), ('data', 'model')),"
        " SPConfig(strategy='full'), 'prefill')\n"
        "struct = jax.eval_shape(lambda: get_model(cfg).init(cfg,"
        " jax.random.PRNGKey(0), 1)[0])\n"
        "init = (('ada/w', 'ada_f/w', 'proj_out/w'),"
        " ('attn/wo/w', 'mlp/wo/w'))\n"
        "params = jax.jit(lambda k: weights.make_params(struct, k,"
        " cfg.n_layers, 'float32', init))(weights.base_key(2**40 + 77))\n"
        "x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, 64))\n"
        "cond = jax.random.normal(jax.random.PRNGKey(6),"
        " (1, 256, cfg.d_model))\n"
        "v = dit_forward(params, cfg, ctx, latents=x, cond=cond,"
        " timesteps=jnp.full((1,), 0.6))\n"
        "step = sample_step(params, cfg, ctx, x, cond, jnp.float32(0.75),"
        " 0.25, SamplerConfig(num_steps=4, guidance_scale=4.0))\n"
        "g = np.load(sys.argv[1])\n"
        "print([bool(np.array_equal(g[k], np.asarray(a))) for k, a in"
        " (('x', x), ('v', v), ('step', step))])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", code,
         str(ROOT / "tests" / "data" / "dit_uniform_parent.npz")], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.strip().splitlines()[-1] == "[True, True, True]"
