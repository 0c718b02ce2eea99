"""Model registry: one uniform API over every assigned architecture.

    bundle = get_model(cfg)
    params, axes = bundle.init(cfg, key, ep_degree)
    loss, aux    = bundle.loss(params, batch, cfg, ctx)          # train
    logits       = bundle.apply(params, batch, cfg, ctx)         # prefill
    out, caches  = bundle.step(params, batch, caches, idx, cfg, ctx)  # decode
    batch        = bundle.input_specs(cfg, shape, abstract=...)  # SDS or data

Batches are plain dicts; modality frontends (vision patches, audio frames)
appear as precomputed embeddings per the stub carve-out.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..configs.shapes import InputShape
from . import dit as dit_mod
from . import lm as lm_mod
from . import whisper as whisper_mod
from .blocks import ParallelContext, Params

Batch = dict[str, Any]


def _xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    init: Callable
    loss: Callable  # (params, batch, cfg, ctx) -> (loss, aux)
    apply: Callable  # (params, batch, cfg, ctx) -> outputs (prefill/forward)
    step: Callable | None  # decode: (params, batch, caches, idx, cfg, ctx)
    init_caches: Callable | None
    input_specs: Callable  # (cfg, shape, abstract=True, key=None) -> Batch


# ---------------------------------------------------------------------------
# LM families (dense / moe / hybrid / ssm / vlm)
# ---------------------------------------------------------------------------

def _lm_inputs(cfg: ModelConfig, shape: InputShape, abstract=True, key=None,
               dtype=None) -> Batch:
    dtype = dtype or cfg.dtype
    b, l = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        batch: Batch = {"tokens": _sds((b, 1), jnp.int32)}
        if cfg.family == "vlm":
            batch["positions"] = _sds((3, b, 1), jnp.int32)
    else:
        batch = {
            "tokens": _sds((b, l), jnp.int32),
            "labels": _sds((b, l), jnp.int32),
        }
        if cfg.family == "vlm":
            # stub frontend: patch+text embeddings and 3D M-RoPE positions
            batch["inputs_embeds"] = _sds((b, l, cfg.d_model), dtype)
            batch["positions"] = _sds((3, b, l), jnp.int32)
    if abstract:
        return batch
    assert key is not None
    return _concretize(batch, key, cfg)


def _concretize(batch: Batch, key: jax.Array, cfg: ModelConfig) -> Batch:
    out = {}
    for name, s in batch.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(s.dtype, jnp.integer):
            hi = max(cfg.vocab, 2)
            out[name] = jax.random.randint(sub, s.shape, 0, hi, s.dtype)
        else:
            out[name] = jax.random.normal(sub, s.shape, s.dtype) * 0.02
    return out


def _lm_loss(params, batch, cfg, ctx):
    logits, aux, _ = lm_mod.lm_forward(
        params, cfg, ctx,
        tokens=batch.get("tokens"),
        inputs_embeds=batch.get("inputs_embeds"),
        positions=batch.get("positions"),
    )
    return _xent(logits, batch["labels"]) + aux, aux


def _lm_apply(params, batch, cfg, ctx, last_only=False):
    logits, _, _ = lm_mod.lm_forward(
        params, cfg, ctx,
        tokens=batch.get("tokens"),
        inputs_embeds=batch.get("inputs_embeds"),
        positions=batch.get("positions"),
        last_only=last_only,
    )
    return logits


def _lm_step(params, batch, caches, cur_index, cfg, ctx):
    logits, _, new_caches = lm_mod.lm_forward(
        params, cfg, ctx,
        tokens=batch.get("tokens"),
        positions=batch.get("positions"),
        caches=caches, cur_index=cur_index,
    )
    return logits[:, -1], new_caches


LM_BUNDLE = ModelBundle(
    init=lm_mod.init_lm,
    loss=_lm_loss,
    apply=_lm_apply,
    step=_lm_step,
    init_caches=lm_mod.init_lm_caches,
    input_specs=_lm_inputs,
)


# ---------------------------------------------------------------------------
# whisper (audio)
# ---------------------------------------------------------------------------

def _whisper_inputs(cfg, shape, abstract=True, key=None, dtype=None):
    dtype = dtype or cfg.dtype
    b, l = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        batch = {
            "tokens": _sds((b, 1), jnp.int32),
            "encoder_out": _sds((b, cfg.encoder_seq, cfg.d_model), dtype),
        }
    else:
        batch = {
            "frames": _sds((b, cfg.encoder_seq, cfg.d_model), dtype),
            "tokens": _sds((b, l), jnp.int32),
            "labels": _sds((b, l), jnp.int32),
        }
    if abstract:
        return batch
    return _concretize(batch, key, cfg)


def _whisper_loss(params, batch, cfg, ctx):
    memory = whisper_mod.encode(params, batch["frames"], cfg, ctx)
    logits, _ = whisper_mod.decode_forward(
        params, cfg, ctx, tokens=batch["tokens"], memory=memory)
    return _xent(logits, batch["labels"]), jnp.zeros((), jnp.float32)


def _whisper_apply(params, batch, cfg, ctx):
    memory = whisper_mod.encode(params, batch["frames"], cfg, ctx)
    logits, _ = whisper_mod.decode_forward(
        params, cfg, ctx, tokens=batch["tokens"], memory=memory)
    return logits


def _whisper_step(params, batch, caches, cur_index, cfg, ctx):
    logits, new_caches = whisper_mod.decode_forward(
        params, cfg, ctx, tokens=batch["tokens"], memory=batch["encoder_out"],
        caches=caches, cur_index=cur_index)
    return logits[:, -1], new_caches


WHISPER_BUNDLE = ModelBundle(
    init=whisper_mod.init_whisper,
    loss=_whisper_loss,
    apply=_whisper_apply,
    step=_whisper_step,
    init_caches=whisper_mod.init_whisper_caches,
    input_specs=_whisper_inputs,
)


# ---------------------------------------------------------------------------
# DiT
# ---------------------------------------------------------------------------

def _dit_inputs(cfg, shape, abstract=True, key=None, dtype=None):
    dtype = dtype or cfg.dtype
    b, t = shape.global_batch, shape.seq_len
    batch = {
        "latents": _sds((b, t, dit_mod.LATENT_CHANNELS), dtype),
        "cond": _sds((b, cfg.text_tokens, cfg.cond_width), dtype),
        "timesteps": _sds((b,), jnp.float32),
        "targets": _sds((b, t, dit_mod.LATENT_CHANNELS), dtype),
    }
    if abstract:
        return batch
    return _concretize(batch, key, cfg)


def _dit_loss(params, batch, cfg, ctx):
    v = dit_mod.dit_forward(params, cfg, ctx, latents=batch["latents"],
                            cond=batch["cond"], timesteps=batch["timesteps"])
    loss = jnp.mean((v.astype(jnp.float32)
                     - batch["targets"].astype(jnp.float32)) ** 2)
    return loss, jnp.zeros((), jnp.float32)


def _dit_apply(params, batch, cfg, ctx):
    return dit_mod.dit_forward(params, cfg, ctx, latents=batch["latents"],
                               cond=batch["cond"], timesteps=batch["timesteps"])


DIT_BUNDLE = ModelBundle(
    init=dit_mod.init_dit,
    loss=_dit_loss,
    apply=_dit_apply,
    step=None,  # diffusion has no AR decode; sampling loops over apply
    init_caches=None,
    input_specs=_dit_inputs,
)


def get_model(cfg: ModelConfig) -> ModelBundle:
    if cfg.family == "audio":
        return WHISPER_BUNDLE
    if cfg.family == "dit":
        return DIT_BUNDLE
    return LM_BUNDLE
