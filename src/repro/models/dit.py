"""Diffusion Transformer (the paper's own workload family).

Latent patches arrive pre-patchified (VAE + patchifier stubbed per
DESIGN.md §6) together with a text-conditioning token sequence
(``cfg.text_tokens`` x ``cfg.cond_width``); the model concatenates
[cond ; latents], runs DiT blocks with the configured SP attention
strategy (bidirectional — DiTs are non-causal), and projects the latent
positions back to the latent channel dim, predicting the flow-matching
velocity.  Two block kinds (``cfg.block``): ``uniform``, the repo's
adaLN-zero block (one modulation for every token), and ``cogvideox``,
CogVideoX's block (configs/cogvideox_5b.py): one LayerNorm for both
streams, then the latent tokens and the text tokens each take their own
shift, scale and gate ("expert" adaLN) from silu(temb), with q/k
LayerNorm and 3-D RoPE in the joint attention, real sinusoidal timestep
features, biased linears, and a final LayerNorm then AdaLayerNorm.

This is the model the serving engine (serving/engine.py) samples with.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..comm import Stream, pipe_handoff
from ..configs.base import ModelConfig
from ..core.pipefusion import (
    KVState,
    drop_rows,
    patch_slices,
    stage_layers,
    update_state_rows,
)
from .blocks import (
    ParallelContext,
    ParamBuilder,
    Params,
    attention,
    init_attention,
    init_linear,
    init_mlp,
    init_norm,
    linear,
    mlp,
    norm,
    sinusoidal_embedding,
    stack_layers,
)

LATENT_CHANNELS = 64
TIME_EMB = 256


def _init_block(key, cfg: ModelConfig):
    b = ParamBuilder(key, dtype=jnp.dtype(cfg.dtype))
    init_norm(b, "ln_attn", cfg.d_model, cfg.norm)
    init_attention(b, cfg)
    init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
    init_mlp(b, cfg)
    if cfg.block == "cogvideox":
        # expert adaLN: (shift, scale, gate) of the latent tokens, then of
        # the text tokens, from silu(temb); before attention and the MLP
        for name in ("ada_attn", "ada_mlp"):
            init_linear(b, name, cfg.time_embed_dim, 6 * cfg.d_model,
                        (None, None), bias=True, init="zeros")
        return b.params, b.axes
    # adaLN-zero: 6 modulation vectors from the time embedding; zero-init so
    # blocks start as identity (DiT paper).
    init_linear(b, "ada", cfg.d_model, 6 * cfg.d_model, ("embed", None),
                init="zeros")
    return b.params, b.axes


def init_dit(cfg: ModelConfig, key: jax.Array, ep_degree: int = 1):
    k1, k2 = jax.random.split(key)
    b = ParamBuilder(k1, dtype=jnp.dtype(cfg.dtype))
    d, cog = cfg.d_model, cfg.block == "cogvideox"
    init_linear(b, "proj_in", LATENT_CHANNELS, d, (None, "embed"), bias=cog)
    init_linear(b, "cond_proj", cfg.cond_width, d, ("embed", "embed_out"),
                bias=cog)
    if cog:
        te = cfg.time_embed_dim
        init_linear(b, "time_mlp1", d, te, ("embed", None), bias=True)
        init_linear(b, "time_mlp2", te, te, (None, None), bias=True)
        init_norm(b, "ln_f", d, cfg.norm)
        init_norm(b, "ln_out", d, cfg.norm)  # the AdaLayerNorm's own norm
        init_linear(b, "ada_f", te, 2 * d, (None, None), bias=True,
                    init="zeros")
    else:
        init_linear(b, "time_mlp1", TIME_EMB, d, (None, "embed"))
        init_linear(b, "time_mlp2", d, d, ("embed", "embed_out"))
        init_norm(b, "ln_f", d, cfg.norm)
        init_linear(b, "ada_f", d, 2 * d, ("embed", None), init="zeros")
    init_linear(b, "proj_out", d, LATENT_CHANNELS, ("embed", None), bias=cog,
                init="zeros")
    params, axes = b.params, b.axes
    lp, la = stack_layers(partial(_init_block, cfg=cfg), cfg.n_layers, k2)
    params["layers"], axes["layers"] = lp, la
    return params, axes


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None]) + shift[:, None]


def _time_embedding(params: Params, cfg: ModelConfig, timesteps: jax.Array,
                    dtype) -> jax.Array:
    if cfg.block == "cogvideox":
        # CogVideoX's Timesteps(d_model, flip_sin_to_cos, freq_shift 0) of
        # t * 1000, then TimestepEmbedding: linear, silu, linear
        half = cfg.d_model // 2
        freqs = jnp.exp(-math.log(10000.0)
                        * jnp.arange(half, dtype=jnp.float32) / half)
        ang = timesteps[:, None] * 1000.0 * freqs
        t_feat = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)],
                                 axis=-1).astype(dtype)
        return linear(jax.nn.silu(linear(t_feat, params["time_mlp1"])),
                      params["time_mlp2"])  # [B, time_embed_dim]
    temb = sinusoidal_embedding(TIME_EMB, TIME_EMB)  # reuse table as freqs
    t_feat = jnp.concatenate(
        [jnp.sin(timesteps[:, None] * 1000.0 * temb[0, : TIME_EMB // 2]),
         jnp.cos(timesteps[:, None] * 1000.0 * temb[0, : TIME_EMB // 2])],
        axis=-1,
    ).astype(dtype)
    return linear(jax.nn.silu(linear(t_feat, params["time_mlp1"])),
                  params["time_mlp2"])  # [B, d]


def _final_projection(params: Params, cfg: ModelConfig, x: jax.Array,
                      t_emb: jax.Array) -> jax.Array:
    if cfg.block == "cogvideox":
        # norm_final (of the float32 residual stream), then AdaLayerNorm
        # (shift, scale) of silu(temb)
        x = norm(x, params["ln_f"], cfg.norm).astype(t_emb.dtype)
        sh, sc = jnp.split(linear(jax.nn.silu(t_emb), params["ada_f"]), 2,
                           axis=-1)
        x = _modulate(norm(x, params["ln_out"], cfg.norm), sh, sc)
        return linear(x, params["proj_out"])
    sh, sc = jnp.split(linear(t_emb, params["ada_f"]), 2, axis=-1)
    x = _modulate(norm(x, params["ln_f"], cfg.norm), sh, sc)
    return linear(x, params["proj_out"])


def _expert_modulate(h, mod, is_text):
    """CogVideoX's per-stream adaLN: ``mod`` [B, 6d] is (shift, scale,
    gate) of the latent tokens then of the text tokens; each row of ``h``
    [B, L, d] takes its stream's.  Returns (modulated h, per-row gate)."""
    sh, sc, g, tsh, tsc, tg = jnp.split(mod, 6, axis=-1)
    pick = lambda lat, txt: jnp.where(is_text, txt[:, None], lat[:, None])
    return h * (1.0 + pick(sc, tsc)) + pick(sh, tsh), pick(g, tg)


def _cogvideox_block(x, lp, cfg: ModelConfig, ctx: ParallelContext,
                     positions, t_act, is_text):
    """One CogVideoXBlock over the joint [text ; latent] rows of ``x``:
    both streams share each LayerNorm, attention and MLP, and each adds
    its own gated residual.  ``x`` is the float32 residual stream; what
    the attention and the MLP read is in ``t_act``'s (the served) dtype."""
    dt = t_act.dtype
    with jax.named_scope("modulate"):
        h, gate = _expert_modulate(norm(x, lp["ln_attn"], cfg.norm).astype(dt),
                                   linear(t_act, lp["ada_attn"]), is_text)
    o, _ = attention(h, lp["attn"], cfg, ctx, positions, causal=False)
    with jax.named_scope("modulate"):
        x = x + (gate * o).astype(x.dtype)
        h, gate = _expert_modulate(norm(x, lp["ln_mlp"], cfg.norm).astype(dt),
                                   linear(t_act, lp["ada_mlp"]), is_text)
    o = mlp(h, lp["mlp"], cfg)
    with jax.named_scope("modulate"):
        return x + (gate * o).astype(x.dtype)


def dit_forward(
    params: Params,
    cfg: ModelConfig,
    ctx: ParallelContext,
    *,
    latents: jax.Array,  # [B, T, LATENT_CHANNELS]
    cond: jax.Array,  # [B, text_tokens, cond_width] (stub text encoder)
    timesteps: jax.Array,  # [B] in [0, 1]
    return_layer_kv: bool = False,
):
    """Returns predicted velocity [B, T, LATENT_CHANNELS].

    With ``return_layer_kv`` also returns a KVState of every layer's
    full-sequence post-RoPE (K, V) — the warmup pass of displaced patch
    pipelining (DESIGN.md §7) uses this to seed the stale-activation
    caches.  The x-path computation is identical either way.
    """
    b_, t_, _ = latents.shape
    x_lat = linear(latents, params["proj_in"])
    x_cond = linear(cond, params["cond_proj"])
    x = jnp.concatenate([x_cond, x_lat], axis=1)
    l_ = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(l_)[None], (b_, l_))
    t_emb = _time_embedding(params, cfg, timesteps, x.dtype)
    if cfg.rope == "rope3d" and t_ % math.prod(cfg.patch_grid):
        raise ValueError(f"{t_} latent tokens are not whole frames of "
                         f"{cfg.patch_grid} patches")
    if cfg.block == "cogvideox":
        if return_layer_kv:
            raise ValueError("return_layer_kv (displaced patch pipelining) "
                             "runs the uniform block only")
        t_act = jax.nn.silu(t_emb)
        is_text = (jnp.arange(l_) < cfg.text_tokens)[None, :, None]
        body = ctx.remat_wrap(lambda x, lp: (_cogvideox_block(
            x, lp, cfg, ctx, positions, t_act, is_text), None))
        # the residual stream stays float32 across the blocks: rounding it
        # to bf16 at each of the 2 x n_layers adds loses more than every
        # bf16 matmul operand together (PERF.md section 2)
        x, _ = lax.scan(body, x.astype(jnp.float32), params["layers"],
                        unroll=cfg.n_layers <= 2)
        return _final_projection(params, cfg, x, t_emb)[:, cfg.text_tokens:]

    def body(x, lp):
        mod = linear(t_emb, lp["ada"])  # [B, 6d]
        sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
        h = _modulate(norm(x, lp["ln_attn"], cfg.norm), sh1, sc1)
        if return_layer_kv:
            o, _, kv = attention(h, lp["attn"], cfg, ctx, positions,
                                 causal=False, return_kv=True)
        else:
            o, _ = attention(h, lp["attn"], cfg, ctx, positions, causal=False)
            kv = None
        x = x + g1[:, None] * o
        h = _modulate(norm(x, lp["ln_mlp"], cfg.norm), sh2, sc2)
        x = x + g2[:, None] * mlp(h, lp["mlp"], cfg)
        return x, kv

    body = ctx.remat_wrap(body)
    x, kv = lax.scan(body, x, params["layers"], unroll=cfg.n_layers <= 2)
    v = _final_projection(params, cfg, x, t_emb)[:, cfg.text_tokens:]
    if return_layer_kv:
        return v, KVState(k=kv[0], v=kv[1])
    return v


def dit_forward_displaced(
    params: Params,
    cfg: ModelConfig,
    ctx: ParallelContext,
    *,
    latents: jax.Array,  # [B, T, LATENT_CHANNELS]
    cond: jax.Array,  # [B, text_tokens, cond_width]
    timesteps: jax.Array,  # [B]
    kv_state: KVState,  # per-layer stale KV from the previous sampler step
    num_patches: int,
    pp: int = 1,
) -> tuple[jax.Array, KVState]:
    """One displaced-pipeline DiT forward (PipeFusion async; DESIGN.md §7).

    The latent sequence is split into ``num_patches`` patches (patch 0 also
    owns the conditioning tokens); each patch runs the full block stack
    with fresh Q/KV for its own rows and one-step-stale KV (``kv_state``)
    for every other row.  Fresh per-layer KV is written back, giving the
    next step its stale state.  Returns (velocity, new KVState).

    The python patch loop realises the same dataflow the pp-stage pipeline
    executes across devices: stage s = layers ``stage_layers(L, pp)[s]``,
    micro-step (p, s) runs patch p's stage-s scan segment.  When the mesh
    carries a ``pp``-sized ``ctx.sp.pp_axis``, every stage boundary is an
    explicit one-sided hand-off over the pipe axis (``comm.pipe_handoff``,
    DESIGN.md §8) instead of a GSPMD-implicit transfer: the HLO then names
    one collective-permute per (patch, boundary) carrying the activation,
    independent of the neighbouring patches' compute — which is what lets
    stage s's compute on patch p overlap patch (p+1)'s transfer, and what
    ``comm.trace`` validates against ``comm_model.hybrid_step_latency``'s
    bubble/overlap assumptions.  Without the axis (single-device tests)
    the hand-off is skipped and the maths is unchanged.
    """
    if cfg.block != "uniform":
        raise ValueError(f"displaced patch pipelining runs the uniform "
                         f"block only, not {cfg.block!r}")
    b_, t_, _ = latents.shape
    text = cfg.text_tokens
    stages = stage_layers(cfg.n_layers, pp)
    slices = patch_slices(text, t_, num_patches)
    pp_axis = ctx.sp.pp_axis
    explicit_handoff = (pp > 1 and pp_axis is not None
                        and pp_axis in ctx.mesh.axis_names
                        and ctx.mesh.shape[pp_axis] == pp)
    stream = Stream("pipe")
    batch_axes = ctx.sp.effective_batch_axes(ctx.mesh)

    x_lat = linear(latents, params["proj_in"])
    x_cond = linear(cond, params["cond_proj"])
    x_full = jnp.concatenate([x_cond, x_lat], axis=1)
    total = x_full.shape[1]
    t_emb = _time_embedding(params, cfg, timesteps, x_full.dtype)

    new_state = kv_state
    vel_chunks = []
    for start, length in slices:
        xp = lax.dynamic_slice_in_dim(x_full, start, length, axis=1)
        pos = jnp.broadcast_to(jnp.arange(start, start + length)[None],
                               (b_, length))
        # stale KV for every NON-resident row, per layer: [L, B, T-len, ...]
        ek = drop_rows(kv_state.k, start, length, axis=2)
        ev = drop_rows(kv_state.v, start, length, axis=2)

        def body(x, xs):
            lp, ek_l, ev_l = xs
            mod = linear(t_emb, lp["ada"])
            sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
            h = _modulate(norm(x, lp["ln_attn"], cfg.norm), sh1, sc1)
            o, _, kv = attention(h, lp["attn"], cfg, ctx, pos, causal=False,
                                 extra_kv=(ek_l, ev_l), return_kv=True)
            x = x + g1[:, None] * o
            h = _modulate(norm(x, lp["ln_mlp"], cfg.norm), sh2, sc2)
            x = x + g2[:, None] * mlp(h, lp["mlp"], cfg)
            return x, kv

        # stage-segmented scan: stage s runs its n_layers/pp blocks, then
        # hands the activation to stage s+1 over the pipe axis
        kp_segs, vp_segs = [], []
        for s, (l0, cnt) in enumerate(stages):
            seg = jax.tree.map(lambda a: a[l0:l0 + cnt], params["layers"])
            xp, (kp_s, vp_s) = lax.scan(body, xp,
                                        (seg, ek[l0:l0 + cnt], ev[l0:l0 + cnt]),
                                        unroll=cnt <= 2)
            kp_segs.append(kp_s)
            vp_segs.append(vp_s)
            if explicit_handoff and s < pp - 1:
                xp = pipe_handoff(xp, ctx.mesh, pp_axis,
                                  batch_axes=batch_axes, stream=stream)
        kp = jnp.concatenate(kp_segs, axis=0)
        vp = jnp.concatenate(vp_segs, axis=0)
        new_state = update_state_rows(new_state, kp, vp, start)
        vp_out = _final_projection(params, cfg, xp, t_emb)
        if start == 0:  # patch 0 carries the conditioning tokens
            vp_out = vp_out[:, text:]
        vel_chunks.append(vp_out)
    assert total == text + t_
    return jnp.concatenate(vel_chunks, axis=1), new_state
