"""Unified decoder-only language model covering the dense / moe / vlm /
hybrid / ssm families.

One ``lax.scan`` over stacked layer weights; the per-layer body dispatches
on family:

  sequence mixer:  attention (dense/moe/vlm)
                   attention ∥ SSD branch, mean-combined   (hymba)
                   RWKV6 time-mix                           (rwkv6)
  channel mixer :  MLP | MoE (+shared experts / dense residual) |
                   RWKV6 channel-mix

Decode mode threads per-layer caches through the scan:
  attention: (k_cache, v_cache) sharded over SP axes on the seq dim
  rwkv6    : (shift_tm, shift_cm, wkv state)
  hymba    : attention caches + SSD state
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from . import ssm
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
    stack_layers,
)
from .moe import init_moe, moe_block, padded_n_experts

GLOBAL_WINDOW = 1 << 30  # "window" value meaning full/global attention


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key: jax.Array, cfg: ModelConfig, ep_degree: int) -> tuple[Params, Params]:
    b = ParamBuilder(key, dtype=jnp.dtype(cfg.dtype))
    if cfg.family == "ssm":  # rwkv6
        _init_rwkv_layer(b, cfg)
        return b.params, b.axes
    init_norm(b, "ln_attn", cfg.d_model, cfg.norm)
    init_attention(b, cfg)
    if cfg.family == "hybrid":
        _init_ssd_branch(b, cfg)
    init_norm(b, "ln_mlp", cfg.d_model, cfg.norm)
    if cfg.family == "moe":
        init_moe(b, cfg, n_pad_experts=padded_n_experts(cfg, ep_degree) - cfg.moe.n_experts)
        if cfg.moe.n_shared_experts:
            init_mlp(b, cfg, prefix="shared_mlp",
                     d_ff=cfg.moe.moe_d_ff * cfg.moe.n_shared_experts)
        if cfg.moe.dense_residual:
            init_mlp(b, cfg, prefix="dense_mlp", d_ff=cfg.d_ff)
    else:
        init_mlp(b, cfg)
    return b.params, b.axes


def _init_rwkv_layer(b: ParamBuilder, cfg: ModelConfig) -> None:
    d = cfg.d_model
    h = cfg.ssm.n_ssm_heads
    n = d // h
    init_norm(b, "ln_tm", d, cfg.norm)
    init_norm(b, "ln_cm", d, cfg.norm)
    for name in ("r", "k", "v", "g"):
        b.add(f"tm/mu_{name}", (d,), ("embed_norm",), init="zeros")
        init_linear(b, f"tm/w{name}", d, d, ("embed", "heads_flat"))
    b.add("tm/mu_w", (d,), ("embed_norm",), init="zeros")
    b.add("tm/w0", (d,), ("embed_norm",), init="zeros")
    lora = max(32, d // 32)
    init_linear(b, "tm/wlora_a", d, lora, ("embed", None))
    init_linear(b, "tm/wlora_b", lora, d, (None, "embed"), init="zeros")
    b.add("tm/u", (h, n), ("ssm_heads", None), init="zeros")
    b.add("tm/gn_scale", (d,), ("embed_norm",), init="ones")
    init_linear(b, "tm/wo", d, d, ("heads_flat", "embed"),
                scale=d ** -0.5 / (2 * cfg.n_layers) ** 0.5)
    # channel mix
    b.add("cm/mu_k", (d,), ("embed_norm",), init="zeros")
    b.add("cm/mu_r", (d,), ("embed_norm",), init="zeros")
    init_linear(b, "cm/wk", d, cfg.d_ff, ("embed", "mlp"))
    init_linear(b, "cm/wv", cfg.d_ff, d, ("mlp", "embed"),
                scale=cfg.d_ff ** -0.5 / (2 * cfg.n_layers) ** 0.5)
    init_linear(b, "cm/wr", d, d, ("embed", "embed_out"))


def _init_ssd_branch(b: ParamBuilder, cfg: ModelConfig) -> None:
    d = cfg.d_model
    h = cfg.ssm.n_ssm_heads
    p_ = (d * cfg.ssm.expand) // h
    n = cfg.ssm.state_size
    init_linear(b, "ssd/in_x", d, h * p_, ("embed", "heads_flat"))
    init_linear(b, "ssd/in_z", d, h * p_, ("embed", "heads_flat"))
    init_linear(b, "ssd/in_dt", d, h, ("embed", None))
    init_linear(b, "ssd/in_b", d, h * n, ("embed", None))
    init_linear(b, "ssd/in_c", d, h * n, ("embed", None))
    b.add("ssd/a_log", (h,), ("ssm_heads",), init="zeros")
    b.add("ssd/norm_scale", (h * p_,), ("embed_norm",), init="ones")
    init_linear(b, "ssd/out", h * p_, d, ("heads_flat", "embed"),
                scale=(h * p_) ** -0.5 / (2 * cfg.n_layers) ** 0.5)


def init_lm(cfg: ModelConfig, key: jax.Array, ep_degree: int = 1) -> tuple[Params, Params]:
    ke, kl, kf = jax.random.split(key, 3)
    params: Params = {}
    axes: Params = {}
    b = ParamBuilder(ke, dtype=jnp.dtype(cfg.dtype))
    if cfg.vocab:
        b.add("embed", (cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02)
        if not cfg.tie_embeddings:
            init_linear(b, "lm_head", cfg.d_model, cfg.vocab, ("embed", "vocab"))
    init_norm(b, "ln_f", cfg.d_model, cfg.norm)
    params.update(b.params)
    axes.update(b.axes)
    lp, la = stack_layers(partial(_init_layer, cfg=cfg, ep_degree=ep_degree),
                          cfg.n_layers, kl)
    params["layers"] = lp
    axes["layers"] = la
    return params, axes


# ---------------------------------------------------------------------------
# family-specific mixers
# ---------------------------------------------------------------------------

def _token_shift(x: jax.Array, ctx: ParallelContext, prev: jax.Array | None):
    """x_{t-1} with cross-device boundary handling (seq sharded over SP)."""
    if prev is not None:  # decode: prev token provided from cache
        return prev
    sp_axes = ctx.sp.sp_axes
    size = math.prod(ctx.mesh.shape[a] for a in sp_axes)

    def body(xl):
        last = xl[:, -1:]
        if size > 1:
            perm = [(i, i + 1) for i in range(size - 1)]
            recv = lax.ppermute(last, sp_axes, perm)
            rank = lax.axis_index(sp_axes)
            recv = jnp.where(rank > 0, recv, jnp.zeros_like(recv))
        else:
            recv = jnp.zeros_like(last)
        return jnp.concatenate([recv, xl[:, :-1]], axis=1)

    ba = ctx.sp.batch_axes
    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=P(ba, sp_axes, None), out_specs=P(ba, sp_axes, None),
        check_vma=False,
    )
    return fn(x)


def _rwkv_time_mix(x, p, cfg, ctx: ParallelContext, cache):
    d = cfg.d_model
    h = cfg.ssm.n_ssm_heads
    n = d // h
    b_, l_, _ = x.shape
    prev = cache["shift_tm"] if ctx.decode else None
    xx = _token_shift(x, ctx, prev)
    mix = lambda mu: x + (xx - x) * mu
    r = linear(mix(p["mu_r"]), p["wr"]).reshape(b_, l_, h, n)
    k = linear(mix(p["mu_k"]), p["wk"]).reshape(b_, l_, h, n)
    v = linear(mix(p["mu_v"]), p["wv"]).reshape(b_, l_, h, n)
    g = jax.nn.silu(linear(mix(p["mu_g"]), p["wg"]))
    xw = mix(p["mu_w"])
    dd = jnp.einsum("bld,dr->blr", xw, p["wlora_a"]["w"].astype(x.dtype))
    dd = jnp.einsum("blr,rd->bld", jnp.tanh(dd), p["wlora_b"]["w"].astype(x.dtype))
    w = jnp.exp(-jnp.exp(p["w0"].astype(jnp.float32) + dd.astype(jnp.float32)))
    w = w.reshape(b_, l_, h, n)

    if ctx.decode:
        s = cache["wkv_state"]
        o, s_new = ssm.rwkv6_decode_step(
            r[:, 0], k[:, 0], v[:, 0], w[:, 0], p["u"], s)
        o = o[:, None]
        new_cache = {"shift_tm": x, "wkv_state": s_new}
    else:
        o = _distributed_scan_rwkv(r, k, v, w, p["u"], ctx)
        new_cache = None
    # per-head group norm
    o = o.reshape(b_, l_, h, n)
    mu = jnp.mean(o, axis=-1, keepdims=True)
    var = jnp.var(o, axis=-1, keepdims=True)
    o = (o - mu) * jax.lax.rsqrt(var + 1e-5)
    o = o.reshape(b_, l_, d) * p["gn_scale"].astype(jnp.float32)
    o = o.astype(x.dtype) * g
    return linear(o, p["wo"]), new_cache


def _distributed_scan_rwkv(r, k, v, w, u, ctx: ParallelContext):
    sp_axes = ctx.sp.sp_axes
    size = math.prod(ctx.mesh.shape[a] for a in sp_axes)
    ba = ctx.sp.batch_axes

    def body(r, k, v, w):
        res = ssm.rwkv6_chunk_scan(r, k, v, w, u)
        s_in = ssm.distributed_state_in(res.a_dev, res.s_out, sp_axes, size)
        return ssm.rwkv6_apply_influence(res.out, res.infl, s_in)

    spec = P(ba, sp_axes, None, None)
    fn = jax.shard_map(body, mesh=ctx.mesh, in_specs=(spec,) * 4,
                       out_specs=spec, check_vma=False)
    return fn(r, k, v, w)


# ---------------------------------------------------------------------------
# layer body + full forward
# ---------------------------------------------------------------------------

def _layer(x, lp, cfg, ctx: ParallelContext, positions, window, cache, cur_index):
    """One transformer layer.  Returns (x, aux_loss, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}

    if cfg.family == "ssm":
        o, nc = _rwkv_time_mix(norm(x, lp["ln_tm"], cfg.norm), lp["tm"], cfg, ctx,
                               cache)
        if nc:
            new_cache.update(nc)
        x = x + o
        h_ = norm(x, lp["ln_cm"], cfg.norm)
        prev = cache["shift_cm"] if ctx.decode else None
        xx = _token_shift(h_, ctx, prev)
        if ctx.decode:
            new_cache["shift_cm"] = h_
        km = h_ + (xx - h_) * lp["cm"]["mu_k"]
        rm = h_ + (xx - h_) * lp["cm"]["mu_r"]
        kk = jnp.square(jax.nn.relu(linear(km, lp["cm"]["wk"])))
        x = x + jax.nn.sigmoid(linear(rm, lp["cm"]["wr"])) * linear(kk, lp["cm"]["wv"])
        return x, aux, new_cache

    h_ = norm(x, lp["ln_attn"], cfg.norm)
    kv_cache = (cache["k"], cache["v"]) if ctx.decode else None
    attn_out, upd_cache = attention(
        h_, lp["attn"], cfg, ctx, positions,
        window=window, kv_cache=kv_cache, cur_index=cur_index,
    )
    if ctx.decode and upd_cache is not None:
        new_cache["k"], new_cache["v"] = upd_cache

    if cfg.family == "hybrid":
        ssd_out, nc = _hymba_ssd(h_, lp["ssd"], cfg, ctx, cache)
        if nc:
            new_cache.update(nc)
        x = x + (attn_out + ssd_out) * 0.5
    else:
        x = x + attn_out

    h_ = norm(x, lp["ln_mlp"], cfg.norm)
    if cfg.family == "moe":
        y, aux = moe_block(h_, lp["moe"], cfg, ctx)
        if cfg.moe.n_shared_experts:
            y = y + mlp(h_, lp["shared_mlp"], cfg)
        if cfg.moe.dense_residual:
            y = y + mlp(h_, lp["dense_mlp"], cfg)
        x = x + y
        aux = aux * cfg.moe.router_aux_coef
    else:
        x = x + mlp(h_, lp["mlp"], cfg)
    return x, aux, new_cache


def _hymba_ssd(x, p, cfg, ctx, cache):
    """SSD branch wrapper returning (out, new_cache_or_None)."""
    h = cfg.ssm.n_ssm_heads
    d_in = cfg.d_model * cfg.ssm.expand
    p_ = d_in // h
    n = cfg.ssm.state_size
    b_, l_, _ = x.shape
    xs = linear(x, p["in_x"]).reshape(b_, l_, h, p_)
    z = jax.nn.silu(linear(x, p["in_z"]))
    dt = jax.nn.softplus(linear(x, p["in_dt"]))
    bm = linear(x, p["in_b"]).reshape(b_, l_, h, n)
    cm = linear(x, p["in_c"]).reshape(b_, l_, h, n)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))

    if ctx.decode:
        s = cache["ssd_state"]
        o, s_new = ssm.ssd_decode_step(xs[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], a, s)
        o = o[:, None].astype(x.dtype)
        nc = {"ssd_state": s_new}
    else:
        sp_axes = ctx.sp.sp_axes
        size = math.prod(ctx.mesh.shape[ax] for ax in sp_axes)
        ba = ctx.sp.batch_axes

        def body(xs, dt, bm, cm):
            res = ssm.ssd_chunk_scan(xs, dt, bm, cm, a)
            s_in = ssm.distributed_state_in(res.a_dev, res.s_out, sp_axes, size)
            return ssm.ssd_apply_influence(res.out, res.infl, s_in)

        s4 = P(ba, sp_axes, None, None)
        s3 = P(ba, sp_axes, None)
        fn = jax.shard_map(body, mesh=ctx.mesh, in_specs=(s4, s3, s4, s4),
                           out_specs=s4, check_vma=False)
        o = fn(xs, dt, bm, cm).astype(x.dtype)
        nc = None
    o = o.reshape(b_, l_, d_in)
    of = o.astype(jnp.float32)
    of = of * jax.lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + 1e-6)
    o = (of * p["norm_scale"].astype(jnp.float32)).astype(x.dtype) * z
    return linear(o, p["out"]), nc


def _per_layer_windows(cfg: ModelConfig) -> jax.Array | None:
    """Hymba: layers {0, mid, last} global, rest sliding-window.  Other archs
    with cfg.window: uniform window.  None: fully global (no mask tensor)."""
    if cfg.family == "hybrid" and cfg.window:
        w = jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
        glb = [0, cfg.n_layers // 2, cfg.n_layers - 1]
        return w.at[jnp.array(glb)].set(GLOBAL_WINDOW)
    if cfg.window:
        return jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
    return None


def lm_forward(
    params: Params,
    cfg: ModelConfig,
    ctx: ParallelContext,
    *,
    tokens: jax.Array | None = None,  # [B, L] int32
    inputs_embeds: jax.Array | None = None,  # [B, L, d] (vlm stub frontend)
    positions: jax.Array | None = None,  # [B, L] or [3, B, L] (mrope)
    caches: Params | None = None,  # decode caches, stacked over layers
    cur_index: jax.Array | None = None,
    last_only: bool = False,  # prefill: logits for the final position only
) -> tuple[jax.Array, jax.Array, Params | None]:
    """Returns (logits [B, L, V] (or [B, 1, V] if last_only), aux, caches).

    ``last_only`` is the standard serving-engine optimization: a prefill
    only needs the next-token distribution, so the [B, L, V] logits
    tensor — the largest activation of the whole step — shrinks L×
    (beyond-paper, EXPERIMENTS.md §Perf)."""
    if inputs_embeds is not None:
        x = inputs_embeds
    else:
        x = params["embed"].astype(cfg.dtype)[tokens]
    b_, l_, _ = x.shape
    if positions is None:
        if ctx.decode:
            base = jnp.broadcast_to(cur_index, (b_, 1)).astype(jnp.int32)
        else:
            base = jnp.broadcast_to(jnp.arange(l_)[None], (b_, l_))
        positions = base
        if cfg.rope == "mrope":
            positions = jnp.broadcast_to(base[None], (3, b_, l_))

    windows = _per_layer_windows(cfg)

    def body(carry, xs):
        x, aux = carry
        lp = xs["params"]
        cache = xs.get("cache")
        window = xs.get("window")
        x, a, new_cache = _layer(x, lp, cfg, ctx, positions, window, cache, cur_index)
        return (x, aux + a), new_cache

    xs = {"params": params["layers"]}
    if caches is not None:
        xs["cache"] = caches
    if windows is not None:
        xs["window"] = windows
    # activation-checkpoint policy (ctx.remat) is a §Perf knob: default
    # recomputes the whole layer (incl. the SP attention schedule) in the
    # backward instead of saving ring-step internals.
    body = ctx.remat_wrap(body)
    # depth<=2 unrolls so cost probes see the true per-layer cost
    # (XLA cost_analysis counts while-loop bodies once regardless of trips)
    (x, aux), new_caches = lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs,
                                    unroll=cfg.n_layers <= 2)

    if last_only:
        x = x[:, -1:]
    x = norm(x, params["ln_f"], cfg.norm)
    if cfg.vocab == 0:
        return x, aux, new_caches if caches is not None else None
    if cfg.tie_embeddings:
        logits = jnp.einsum("bld,vd->blv", x, params["embed"].astype(x.dtype))
    else:
        logits = linear(x, params["lm_head"])
    return logits, aux, new_caches if caches is not None else None


def init_lm_caches(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Params:
    """Decode caches stacked over layers (scan xs/ys structure)."""
    nl = cfg.n_layers
    hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    c: Params = {}
    if cfg.family == "ssm":
        h = cfg.ssm.n_ssm_heads
        n = cfg.d_model // h
        c["shift_tm"] = jnp.zeros((nl, batch, 1, cfg.d_model), dtype)
        c["shift_cm"] = jnp.zeros((nl, batch, 1, cfg.d_model), dtype)
        c["wkv_state"] = jnp.zeros((nl, batch, h, n, n), jnp.float32)
        return c
    c["k"] = jnp.zeros((nl, batch, max_len, hkv, hd), dtype)
    c["v"] = jnp.zeros((nl, batch, max_len, hkv, hd), dtype)
    if cfg.family == "hybrid":
        h = cfg.ssm.n_ssm_heads
        p_ = (cfg.d_model * cfg.ssm.expand) // h
        c["ssd_state"] = jnp.zeros((nl, batch, h, p_, cfg.ssm.state_size), jnp.float32)
    return c
