"""Shared transformer building blocks (pure-functional, pytree params).

Conventions:
  * params are nested dicts of jnp arrays; a parallel pytree of *logical
    axis names* is built at init time (see ParamBuilder) and mapped to mesh
    axes by models/sharding.py.
  * layer stacks are ``lax.scan`` over stacked weights (leading "layers"
    dim) — keeps HLO size O(1) in depth.
  * attention dispatches to core.sp_attention (train/prefill) or
    core.decode_attention (decode) based on the ParallelContext.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..core import SPConfig, decode_attention, sp_attention
from ..core.pipefusion import displaced_attention

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

class ParamBuilder:
    """Builds a params pytree and a mirrored logical-axes pytree in lockstep,
    so sharding specs can never drift from the actual structure."""

    def __init__(self, key: jax.Array, dtype=jnp.float32):
        self.key = key
        self.dtype = dtype
        self.params: Params = {}
        self.axes: Params = {}

    def _next(self) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sub

    def add(self, name: str, shape: tuple[int, ...], logical: tuple[str | None, ...],
            init: str = "normal", scale: float | None = None) -> None:
        assert len(shape) == len(logical), (name, shape, logical)
        if init == "normal":
            if scale is None:
                scale = shape[0] ** -0.5  # fan-in
            arr = jax.random.normal(self._next(), shape, self.dtype) * scale
        elif init == "zeros":
            arr = jnp.zeros(shape, self.dtype)
        elif init == "ones":
            arr = jnp.ones(shape, self.dtype)
        else:
            raise ValueError(init)
        _nested_set(self.params, name, arr)
        _nested_set(self.axes, name, logical)


def _nested_set(d: dict, path: str, val) -> None:
    keys = path.split("/")
    for k in keys[:-1]:
        d = d.setdefault(k, {})
    d[keys[-1]] = val


def stack_layers(init_fn: Callable[[jax.Array], tuple[Params, Params]],
                 n_layers: int, key: jax.Array) -> tuple[Params, Params]:
    """vmap a per-layer init over layer keys -> stacked params with a
    leading 'layers' logical axis."""
    keys = jax.random.split(key, n_layers)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, axes = init_fn(key)  # structure only
    axes = jax.tree.map(
        lambda a: ("layers",) + tuple(a), axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    return params, axes


# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Everything a model needs to know about how it is distributed."""

    mesh: jax.sharding.Mesh
    sp: SPConfig
    mode: str = "train"  # train | prefill | decode
    # activation-checkpoint policy for the layer scan (train mode):
    #   full — recompute everything (min HBM);  dots — save matmul outputs
    #   (jax dots_with_no_batch_dims_saveable);  none — save all residuals
    remat: str = "full"
    # decode-mode MoE: gather tokens over 'data' instead of all-gathering
    # FSDP'd expert weights every step (beyond-paper, §Perf)
    ep_token_gather: bool = False

    @property
    def decode(self) -> bool:
        return self.mode == "decode"

    def remat_wrap(self, body):
        if self.mode != "train" or self.remat == "none":
            return body
        if self.remat == "dots":
            return jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        return jax.checkpoint(body)


# ---------------------------------------------------------------------------
# basic ops
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(dt)


QK_NORM_EPS = 1e-6  # CogVideoX's per-head q/k LayerNorm


def norm(x: jax.Array, p: Params, kind: str, eps: float = 1e-5) -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"], eps)


def init_norm(b: ParamBuilder, name: str, d: int, kind: str) -> None:
    b.add(f"{name}/scale", (d,), ("embed_norm",), init="ones")
    if kind == "layernorm":
        b.add(f"{name}/bias", (d,), ("embed_norm",), init="zeros")


def linear(x: jax.Array, p: Params) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, p["w"].astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def init_linear(b: ParamBuilder, name: str, d_in: int, d_out: int,
                logical: tuple[str | None, str | None], bias: bool = False,
                init: str = "normal", scale: float | None = None) -> None:
    b.add(f"{name}/w", (d_in, d_out), logical, init=init, scale=scale)
    if bias:
        b.add(f"{name}/b", (d_out,), (logical[1],), init="zeros")


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# rotary position embeddings (all assigned variants)
# ---------------------------------------------------------------------------

def _rope_angles(positions: jax.Array, rot_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """positions [...] -> (sin, cos) of shape [..., rot_dim // 2]."""
    freqs = theta ** (-jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(ang), jnp.cos(ang)


def _rotate(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Rotate pairs (x[..., :r/2], x[..., r/2:]) — GPT-NeoX convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(
    q: jax.Array,  # [B, L, H, D]
    k: jax.Array,
    positions: jax.Array,  # [B, L] or [3, B, L] for mrope
    *,
    variant: str,
    theta: float,
    rope_pct: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    if variant in ("none", "sinusoidal"):
        return q, k
    d = q.shape[-1]
    if variant == "rope2d":
        rot = d // 2  # chatglm: rotary on half the head dim
    else:
        rot = int(d * rope_pct) // 2 * 2

    def rot_fn(x):
        xr, xp = x[..., :rot], x[..., rot:]
        if variant == "mrope":
            # 3 position components (t, h, w) over 3 sections of the rotary
            # half-dims (qwen2-vl §2.1); section sizes ~ equal thirds.
            half = rot // 2
            s1, s2 = half // 3, 2 * (half // 3)
            sin, cos = [], []
            for c, (lo, hi) in enumerate(((0, s1), (s1, s2), (s2, half))):
                freqs = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
                ang = positions[c][..., None].astype(jnp.float32) * freqs[lo:hi]
                sin.append(jnp.sin(ang))
                cos.append(jnp.cos(ang))
            sin = jnp.concatenate(sin, axis=-1)[:, :, None, :]
            cos = jnp.concatenate(cos, axis=-1)[:, :, None, :]
        else:
            sin, cos = _rope_angles(positions, rot, theta)
            sin, cos = sin[:, :, None, :], cos[:, :, None, :]
        return jnp.concatenate([_rotate(xr, sin, cos).astype(x.dtype), xp], axis=-1)

    return rot_fn(q), rot_fn(k)


def apply_rope3d(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
    """CogVideoX's 3-D rotary embedding of x [B, L, H, D] at ``positions``
    [B, L].  Position p >= ``cfg.text_tokens`` is latent token
    p - text_tokens, at (frame, row, col) of frames of ``cfg.patch_grid``
    (rows, cols) patches; the head dim splits into ``cfg.rope_axes``
    slices rotated by frame, row and col, each in interleaved pairs
    (x[2i], x[2i+1]) at angle coord * theta^(-2i / slice width).  The text
    tokens ahead are not rotated."""
    rows, cols = cfg.patch_grid
    p = positions - cfg.text_tokens
    coords = (p // (rows * cols), (p // cols) % rows, p % cols)
    ang = jnp.concatenate(
        [c[..., None].astype(jnp.float32)
         * cfg.rope_theta ** (-jnp.arange(0, w, 2, dtype=jnp.float32) / w)
         for c, w in zip(coords, cfg.rope_axes)], axis=-1)
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    rot = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                    axis=-1).reshape(x.shape)
    return jnp.where((p >= 0)[:, :, None, None], rot, x).astype(x.dtype)


def sinusoidal_embedding(length: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal positional table [length, d]."""
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = jnp.arange(length)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------

def init_attention(b: ParamBuilder, cfg, prefix: str = "attn",
                   cross: bool = False) -> None:
    d, hq, hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    init_linear(b, f"{prefix}/wq", d, hq * hd, ("embed", "heads_flat"), bias=cfg.qkv_bias)
    init_linear(b, f"{prefix}/wk", d, hkv * hd, ("embed", "kv_heads_flat"), bias=cfg.qkv_bias)
    init_linear(b, f"{prefix}/wv", d, hkv * hd, ("embed", "kv_heads_flat"), bias=cfg.qkv_bias)
    init_linear(b, f"{prefix}/wo", hq * hd, d, ("heads_flat", "embed"),
                bias=cfg.proj_bias,
                scale=(hq * hd) ** -0.5 / (2 * cfg.n_layers) ** 0.5)
    if cfg.qk_norm:
        for name in ("q_norm", "k_norm"):
            init_norm(b, f"{prefix}/{name}", hd, "layernorm")


def attention(
    x: jax.Array,  # [B, L, d]
    p: Params,
    cfg,
    ctx: ParallelContext,
    positions: jax.Array,
    *,
    window: int | jax.Array | None = None,
    kv_cache: tuple[jax.Array, jax.Array] | None = None,
    cur_index: jax.Array | None = None,
    xkv: jax.Array | None = None,  # cross-attention source (whisper decoder)
    causal: bool | None = None,
    extra_kv: tuple[jax.Array, jax.Array] | None = None,
    return_kv: bool = False,
):
    """Returns (output [B, L, d], updated kv_cache or None).

    ``extra_kv`` — one-step-stale full-sequence KV of the *non-resident*
    rows for displaced patch pipelining (PipeFusion; DESIGN.md §7): K is
    already post-RoPE, and the patch's fresh KV is merged with it via the
    Appendix-C partial algebra instead of the SP schedule (the resident
    patch and the stale rows have different sequence lengths, so the
    equal-shard SP collectives don't apply).  Only valid for
    non-causal, unwindowed attention (DiT).

    ``return_kv`` — additionally return this call's (post-RoPE K, V) as a
    third element, so the sampler can populate the stale-KV state.
    """
    b_, l_, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    causal = cfg.causal if causal is None else causal
    src = x if xkv is None else xkv

    # named scopes (qkv with qk_prep inside, attn, attn_out; mlp in
    # ``mlp``; sp_comm in core/ around the SP exchanges) are metadata only:
    # they prefix the ops' names in the device trace, so a profile splits
    # a block's device time by part
    with jax.named_scope("qkv"):
        q = linear(x, p["wq"]).reshape(b_, l_, hq, hd)
        k = linear(src, p["wk"]).reshape(b_, src.shape[1], hkv, hd)
        v = linear(src, p["wv"]).reshape(b_, src.shape[1], hkv, hd)
        with jax.named_scope("qk_prep"):
            if cfg.qk_norm:
                q = norm(q, p["q_norm"], "layernorm", QK_NORM_EPS)
                k = norm(k, p["k_norm"], "layernorm", QK_NORM_EPS)
            if xkv is None and cfg.rope == "rope3d":
                q, k = (apply_rope3d(a, positions, cfg) for a in (q, k))
            elif xkv is None:  # no rope on cross-attention
                q, k = apply_rope(q, k, positions, variant=cfg.rope,
                                  theta=cfg.rope_theta, rope_pct=cfg.rope_pct)

    with jax.named_scope("attn"):
        if extra_kv is not None:
            assert not ctx.decode and xkv is None
            assert not causal and window is None, (
                "displaced attention is DiT-only (bidirectional, unwindowed)")
            o = displaced_attention(q, k, v, extra_kv[0], extra_kv[1])
            new_cache = None
        elif ctx.decode and xkv is None:
            assert kv_cache is not None and cur_index is not None
            kc, vc = kv_cache
            o, kc, vc = decode_attention(
                q, kc, vc, k, v, cur_index,
                mesh=ctx.mesh, cfg=ctx.sp, window=window,
            )
            new_cache = (kc, vc)
        elif ctx.decode:  # cross-attention during decode: q len 1 vs memory
            o = sp_attention(q, k, v, mesh=ctx.mesh, cfg=_xattn_cfg(ctx.sp),
                             causal=False, window=None)
            new_cache = kv_cache
        else:
            o = sp_attention(q, k, v, mesh=ctx.mesh, cfg=ctx.sp,
                             causal=causal, window=_static_window(window))
            new_cache = None
    with jax.named_scope("attn_out"):
        o = o.reshape(b_, l_, hq * hd)
        out = linear(o, p["wo"])
    if return_kv:
        return out, new_cache, (k, v)
    return out, new_cache


def _static_window(window):
    """sp_attention's mask plumbing accepts traced windows; None stays None."""
    return window


def _xattn_cfg(sp: SPConfig) -> SPConfig:
    """Cross-attention with a decode-mode 1-token q: run unsharded (the
    encoder memory is small relative to self-attention caches)."""
    return dataclasses.replace(sp, strategy="full")


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(b: ParamBuilder, cfg, prefix: str = "mlp", d_ff: int | None = None,
             logical_ff: str = "mlp") -> None:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    bias = cfg.proj_bias
    if cfg.act in ("swiglu", "geglu"):
        init_linear(b, f"{prefix}/wi_gate", d, ff, ("embed", logical_ff),
                    bias=bias)
    init_linear(b, f"{prefix}/wi_up", d, ff, ("embed", logical_ff), bias=bias)
    init_linear(b, f"{prefix}/wo", ff, d, (logical_ff, "embed"), bias=bias,
                scale=ff ** -0.5 / (2 * cfg.n_layers) ** 0.5)


@jax.named_scope("mlp")
def mlp(x: jax.Array, p: Params, cfg) -> jax.Array:
    if cfg.act == "swiglu":
        h = jax.nn.silu(linear(x, p["wi_gate"])) * linear(x, p["wi_up"])
    elif cfg.act == "geglu":
        h = gelu(linear(x, p["wi_gate"])) * linear(x, p["wi_up"])
    else:
        h = gelu(linear(x, p["wi_up"]))
    return linear(h, p["wo"])
