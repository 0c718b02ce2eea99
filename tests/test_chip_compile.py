"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so ``jax.jit(...).lower(...)
.compile()`` against the devices of a described ``v5e:2x2`` topology
raises what the chip's compiler would raise: block shapes that do not
tile, kernels that cannot be partitioned, programs that do not fit HBM.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and every xdist worker imports this
file), and the persistent compilation cache is off around the compiles (a
compile for a described chip is written to it but cannot be read back).
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.comm.pallas_backend import _tpu_remote_put
from repro.compat import make_mesh
from repro.configs import get_config, get_reduced
from repro.configs.shapes import InputShape
from repro.core import SPConfig
from repro.core.ring import FlashLowering
from repro.kernels.flash_mqkv import flash_mqkv
from repro.kernels.ring_flash import ring_flash_step
from repro.models import ParallelContext, get_model
from repro.models.dit import LATENT_CHANNELS
from repro.serving import SamplerConfig
from repro.serving.sampler import sample_step
from repro.train import AdamWConfig
from repro.train.optimizer import init_adamw
from repro.train.trainer import make_train_step

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip
TOKENS = 4096 + get_config("flux-12b").text_tokens  # 1024^2 image + text


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", ["flux-12b", "cogvideox-5b"])
@pytest.mark.parametrize("fused", [False, True], ids=["flash_mqkv",
                                                      "ring_flash"])
def test_attention_kernel_compiles(one_chip, arch, fused):
    """Both kernels at the DiTs' head geometry (24 heads x 128 / x 64),
    bf16: flash_mqkv fresh, ring_flash with carried (O', l, m) state."""
    cfg = get_config(arch)
    bh, d = cfg.n_heads, cfg.resolved_head_dim
    x = _sds((bh, TOKENS, d), jnp.bfloat16, one_chip)
    pos = _sds((TOKENS,), jnp.int32, one_chip)
    state = (_sds((bh, TOKENS, d), jnp.float32, one_chip),
             _sds((bh, TOKENS), jnp.float32, one_chip),
             _sds((bh, TOKENS), jnp.float32, one_chip))
    if fused:
        fn = functools.partial(ring_flash_step, finalize=False,
                               interpret=False)
        args = (x, x, x, pos, pos, state)
        step = lambda q, k, v, qp, kp, st: fn(q, k, v, qp, kp, state=st)
    else:
        step = functools.partial(flash_mqkv, interpret=False)
        args = (x, x, x, pos, pos)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mesh_shape,route", [((1, 4), ("model",)),
                                              ((2, 2), ("data", "model"))])
def test_remote_put_compiles(topo, mesh_shape, route):
    """The TPU branch of the Pallas channel: an in-kernel remote copy
    along a 4-rank ring inside shard_map — over one axis of a mesh whose
    other axis is carried along, and over both axes jointly (the device
    id is a coordinate over the route's axes)."""
    from repro.comm.channel import shift_perm

    mesh = make_mesh(mesh_shape, ("data", "model"), devices=topo.devices)
    spec = P(None, route)
    x = _sds((8, 4 * 1024), jnp.bfloat16, NamedSharding(mesh, spec))

    def body(a, b):
        return _tpu_remote_put((a, b), route, shift_perm(4))

    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), check_vma=False)
    compiled = jax.jit(fn).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _dit_step(mesh, sp, tokens, sharding, arch="flux-12b", guidance=1.0):
    """The served sampler step of ``arch`` at published widths, depth cut
    to 2 blocks, bf16; shapes only."""
    cfg = dataclasses.replace(get_config(arch), n_layers=2)
    bundle = get_model(cfg)
    params = jax.eval_shape(
        lambda: bundle.init(cfg, jax.random.PRNGKey(0), 1)[0])
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), params)
    ctx = ParallelContext(mesh, sp, "prefill")
    sc = SamplerConfig(num_steps=4, guidance_scale=guidance)
    x = _sds((1, tokens, LATENT_CHANNELS), jnp.bfloat16, sharding)
    cond = _sds((1, cfg.text_tokens, cfg.cond_width), jnp.bfloat16, sharding)
    t = _sds((), jnp.float32, sharding)

    def step(params, x, cond, t):
        return sample_step(params, cfg, ctx, x, cond, t, 0.25, sc)

    return jax.jit(step).lower(params, x, cond, t).compile()


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < HBM_BYTES


# temp_size_in_bytes of the step below when SP=1 attention materialised
# its [1, 24, 4352, 4352] scores (the parent of the flash lowering)
MATERIALISED_TEMP_BYTES = 1_052_579_328


def test_flux_step_one_chip(topo, one_chip, monkeypatch):
    """SP=1 at 4096 tokens: where JAX reports a TPU, attention is the
    flash kernel, and the step holds no score matrix."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    sp = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
    compiled = _dit_step(mesh, sp, 4096, one_chip)
    _assert_fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < MATERIALISED_TEMP_BYTES, temp


def test_train_step_one_chip(topo, one_chip, monkeypatch):
    """A one-chip train step where JAX reports a TPU (the launcher's
    default mesh): the forward's SP=1 attention is the flash kernel, and
    the gradient, the oracle's, compiles beside it.  A reduced causal LM
    with 128-wide heads."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_reduced("qwen2-1.5b"), head_dim=128,
                              dtype="bfloat16", sharding_overrides=())
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    sp = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
    bundle = get_model(cfg)
    sds = lambda a: _sds(a.shape, a.dtype, one_chip)
    params = jax.tree.map(sds, jax.eval_shape(
        lambda: bundle.init(cfg, jax.random.PRNGKey(0), 1)[0]))
    opt = jax.tree.map(sds, jax.eval_shape(init_adamw, params))
    batch = jax.tree.map(sds, bundle.input_specs(
        cfg, InputShape("train", 256, 2, "training"), abstract=True))
    step = make_train_step(cfg, mesh, sp, AdamWConfig())
    compiled = jax.jit(step).lower(params, opt, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_train_step_sp4(topo, monkeypatch):
    """A train step at SP=4 ``swift_torus`` where JAX reports a TPU: the
    forward's torus stages attend through the flash kernel (causal, GQA,
    two Ulysses pairs ringing the KV), and the gradient, the oracle's,
    compiles beside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_reduced("qwen2-1.5b"), head_dim=128,
                              dtype="bfloat16", sharding_overrides=())
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    sp = SPConfig(strategy="swift_torus", sp_axes=("model",),
                  batch_axes=("data",))
    bundle = get_model(cfg)
    sds = lambda a: _sds(a.shape, a.dtype, NamedSharding(mesh, P()))
    params = jax.tree.map(sds, jax.eval_shape(
        lambda: bundle.init(cfg, jax.random.PRNGKey(0), 1)[0]))
    opt = jax.tree.map(sds, jax.eval_shape(init_adamw, params))
    batch = jax.tree.map(sds, bundle.input_specs(
        cfg, InputShape("train", 256, 2, "training"), abstract=True))
    step = make_train_step(cfg, mesh, sp, AdamWConfig())
    text = jax.jit(step).lower(params, opt, batch).compile().as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text


@pytest.mark.parametrize("strategy,backend", [("swift_torus", "xla"),
                                              ("ring", "pallas")])
def test_flux_step_sp4(topo, monkeypatch, strategy, backend):
    """SP=4 at 16384 tokens (a 2048x2048 image).  The Pallas ring takes
    its TPU branches (compiled ring_flash, in-kernel remote put), and the
    XLA torus its flash stage attends, only where JAX reports a TPU
    backend, so the test reports one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    sp = SPConfig(strategy=strategy, sp_axes=("model",),
                  batch_axes=("data",), comm_backend=backend)
    compiled = _dit_step(mesh, sp, 16384, NamedSharding(mesh, P()))
    _assert_fits(compiled)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("masked", [False, True], ids=["open", "causal"])
def test_stage_kernel_compiles(one_chip, masked):
    """The SP stage attend at the video cell's Pull-KV shape: 12 heads x
    64 a chip, the gathered 17,776 rows (four chunks padded to 4,480)
    against a 4,444-row chunk, blocks from the chunk's shape, the running
    state carried in and out.  Causal too: the kernel's q positions tile
    at a block_q that is not a whole 128 lanes."""
    low = FlashLowering.of(1, 4444, 4444, 64, jnp.bfloat16)
    bh, lq, lk = 12, 4 * 4480, 4444
    assert (low.block_q, low.block_k) == (448, 4444)
    q = _sds((bh, lq, 64), jnp.bfloat16, one_chip)
    kv = _sds((bh, lk, 64), jnp.bfloat16, one_chip)
    state = (_sds((bh, lq, 64), jnp.float32, one_chip),
             _sds((bh, lq, 1), jnp.float32, one_chip),
             _sds((bh, lq, 1), jnp.float32, one_chip))
    step = functools.partial(
        flash_mqkv, causal=masked, finalize=False, block_q=low.block_q,
        block_k=low.block_k, masked=masked, keepdims=True, interpret=False)
    compiled = jax.jit(lambda q, k, v, qp, kp, st: step(
        q, k, v, qp, kp, state=st)).lower(
        q, kv, kv, _sds((lq,), jnp.int32, one_chip),
        _sds((lk,), jnp.int32, one_chip), state).compile()
    assert "tpu_custom_call" in compiled.as_text()


# temp_size_in_bytes of the step below in the parent of the SP > 1 flash
# lowering, compiled the same way: its torus stages attended through the
# materialised score block
MATERIALISED_SP4_TEMP_BYTES = 2_655_745_536
# the same in the parent of the in-place torus accumulator: its stages'
# partials went into a filled accumulator by dynamic-update-slice
FILLED_ACC_SP4_TEMP_BYTES = 1_925_607_936
# the gathered-Q accumulator of the step below, 12 heads a chip x 4
# chunks of 4,480 rows: O' and the (l, m) columns
ACC_SHAPES = ("f32[12,17920,64]", "f32[12,17920,1]")
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\((.*)$",
                     re.M)


def _ops(text):
    """(name, result shape without its layout, opcode, op_name) of every
    instruction of an optimized HLO text, fusion bodies included."""
    for name, shape, op, rest in _HLO_OP.findall(text):
        scope = re.search(r'op_name="([^"]*)"', rest)
        yield (name, re.sub(r"\{.*", "", shape), op,
               scope.group(1) if scope else "")


def test_cogvideox_step_sp4(topo, monkeypatch):
    """CogVideoX-5B's block at published widths (2 blocks) on the
    ``cogvideox_t2v_sp4`` cell's video, 13 x 30 x 45 = 17,550 latent
    tokens and 226 of text, with guidance at SP=4 ``swift_torus``, where
    JAX reports a TPU: it compiles, fits a chip, attends through the
    flash kernel in its stages, still exchanges over the torus, and holds
    less than the materialised score blocks took.  The gathered-Q
    accumulator lives in kernel outputs alone: seven kernel calls a
    block-forward (stage 0, three Pull-Q, three Pull-KV), and no XLA op
    under ``attn`` fills (broadcast) or patches (dynamic-update-slice)
    it, nor copies it anywhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices)
    sp = SPConfig(strategy="swift_torus", sp_axes=("model",),
                  batch_axes=("data",))
    compiled = _dit_step(mesh, sp, 17550, NamedSharding(mesh, P()),
                         arch="cogvideox-5b", guidance=6.0)
    _assert_fits(compiled)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < MATERIALISED_SP4_TEMP_BYTES, temp
    assert temp <= FILLED_ACC_SP4_TEMP_BYTES, temp
    ops = list(_ops(text))
    glue = [(name, shape) for name, shape, op, scope in ops
            if "attn/" in scope and (op == "dynamic-update-slice" or (
                op == "broadcast" and shape in ACC_SHAPES))]
    assert not glue, glue[:4]
    copies = [name for name, shape, op, _ in ops
              if op in ("copy", "copy-start") and shape in ACC_SHAPES]
    assert not copies, copies[:4]
    kernels = [name for name, _, op, _ in ops
               if op == "custom-call" and name.startswith("flash_mqkv")]
    assert len(kernels) == 7 * 2 * 2, len(kernels)  # 2 blocks x 2 forwards
