"""Plain float32 reference of the served DiT sampler, in ``jax.numpy`` only.

It imports nothing of the program.  It follows the repo's DiT as the
program builds it: [text ; latent] tokens, uniform adaLN-zero blocks
(LayerNorm, modulate, 1-D RoPE attention, tanh-GELU MLP, gated
residuals), a modulated final LayerNorm and projection, and the
flow-matching Euler step x <- x - dt * v with optional classifier-free
guidance v = v_u + g (v_c - v_u) against an all-zero text embedding.
Weights come from ``bench.weights`` layer by layer, so the reference
holds one block at a time; attention runs in blocks of query rows, so
no score tensor is larger than [B, H, block, L].  Given several chips it
splits each block's token rows among them, and gives the two guidance
branches half of the chips each.

``mode="fp8"`` is the control: the same maths with every matmul operand
rounded to float8_e4m3fn (per-tensor scale), the step below the served
bfloat16.  ``run.py`` never calls it; ``calibrate.py`` does.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
TIME_FEATS = 256
LATENT_CHANNELS = 64
ROPE_THETA = 10000.0
LN_EPS = 1e-5
FP8_MAX = 448.0  # largest finite float8_e4m3fn
# The program takes its timestep frequencies from row 0 of a sinusoid
# table, which is sin(0) = 0 everywhere: its time features are the
# constant [0, ..., 0, 1, ..., 1] whatever t is.  The reference computes
# the model as built (PERF.md, Open questions).
TIME_FREQS = np.zeros(TIME_FEATS // 2, np.float32)


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    head_dim: int
    d_ff: int
    layers: int
    text_tokens: int
    dtype: str  # the served dtype the weights are rounded to

    @classmethod
    def of(cls, config: dict) -> "Dims":
        m = config["model"]
        return cls(m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                   m["n_layers"], config["text_tokens"], m["dtype"])


def top_shapes(n: Dims) -> dict[str, tuple[int, ...]]:
    d = n.d
    return {"proj_in/w": (LATENT_CHANNELS, d), "cond_proj/w": (d, d),
            "time_mlp1/w": (TIME_FEATS, d), "time_mlp2/w": (d, d),
            "ln_f/scale": (d,), "ln_f/bias": (d,), "ada_f/w": (d, 2 * d),
            "proj_out/w": (d, LATENT_CHANNELS)}


def block_shapes(n: Dims) -> dict[str, tuple[int, ...]]:
    d, a = n.d, n.heads * n.head_dim
    return {"ln_attn/scale": (d,), "ln_attn/bias": (d,),
            "attn/wq/w": (d, a), "attn/wk/w": (d, a), "attn/wv/w": (d, a),
            "attn/wo/w": (a, d), "ln_mlp/scale": (d,), "ln_mlp/bias": (d,),
            "mlp/wi_up/w": (d, n.d_ff), "mlp/wo/w": (n.d_ff, d),
            "ada/w": (d, 6 * d)}


@functools.partial(jax.jit, static_argnums=(1,))
def _top(key, n: Dims):
    return {p: W.top_leaf(key, p, s, n.layers, n.dtype)
            for p, s in top_shapes(n).items()}


@functools.partial(jax.jit, static_argnums=(1,))
def _block(key, n: Dims, layer):
    return {p: W.layer_leaf(key, "layers/" + p, s, n.layers, layer, n.dtype)
            for p, s in block_shapes(n).items()}


def _q8(x):
    """Round to float8_e4m3fn with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, mode: str):
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _ln(x, scale, bias):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None]) + shift[:, None]


def _rope(x, positions):
    """GPT-NeoX rotation of the two halves of every head, theta 10000."""
    hd = x.shape[-1]
    freqs = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * freqs  # [L, hd/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q_block(length: int, target: int = 512) -> int:
    """Largest divisor of ``length`` not above ``target``."""
    return max(b for b in range(1, min(length, target) + 1)
               if length % b == 0)


def _attention(h, hq, q0: int, w, n: Dims, mode: str):
    """Attention of the rows ``hq`` (starting at position ``q0``) to every
    row of ``h``."""
    b, length, _ = h.shape
    lq = hq.shape[1]
    q = _mm("bld,da->bla", hq, w["attn/wq/w"], mode)
    k = _mm("bld,da->bla", h, w["attn/wk/w"], mode)
    v = _mm("bld,da->bla", h, w["attn/wv/w"], mode)
    q = _rope(q.reshape(b, lq, n.heads, n.head_dim), q0 + jnp.arange(lq))
    k = _rope(k.reshape(b, length, n.heads, n.head_dim), jnp.arange(length))
    v = v.reshape(b, length, n.heads, n.head_dim)
    blk = _q_block(lq)
    qb = q.reshape(b, lq // blk, blk, n.heads, n.head_dim)

    def one(qi):  # [b, blk, H, hd] against every key
        s = _mm("bqhd,bkhd->bhqk", qi, k, mode) * n.head_dim ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, mode)

    o = jax.lax.map(one, jnp.moveaxis(qb, 1, 0))  # [nb, b, blk, H, hd]
    o = jnp.moveaxis(o, 0, 1).reshape(b, lq, n.heads * n.head_dim)
    return _mm("bla,ad->bld", o, w["attn/wo/w"], mode)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _block_fwd(w, x, t_emb, n: Dims, mode: str, part: int = 0,
               parts: int = 1):
    """One block; returns the rows of token slice ``part`` of ``parts``
    (every row's keys and values are computed, so the slices of one
    block can run on different chips)."""
    mod = _mm("bd,df->bf", t_emb, w["ada/w"], mode)
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
    h = _modulate(_ln(x, w["ln_attn/scale"], w["ln_attn/bias"]), sh1, sc1)
    rows = x.shape[1] // parts
    q0 = part * rows
    x = x[:, q0:q0 + rows]
    x = x + g1[:, None] * _attention(h, h[:, q0:q0 + rows], q0, w, n, mode)
    h = _modulate(_ln(x, w["ln_mlp/scale"], w["ln_mlp/bias"]), sh2, sc2)
    u = jax.nn.gelu(_mm("bld,df->blf", h, w["mlp/wi_up/w"], mode),
                    approximate=True)
    return x + g2[:, None] * _mm("blf,fd->bld", u, w["mlp/wo/w"], mode)


@jax.jit
def _join(parts):
    return jnp.concatenate(parts, axis=1)


@functools.partial(jax.jit, static_argnums=(4,))
def _embed(top, x_lat, cond, t, mode: str):
    x = jnp.concatenate([_mm("bcd,de->bce", cond, top["cond_proj/w"], mode),
                         _mm("btc,cd->btd", x_lat, top["proj_in/w"], mode)],
                        axis=1)
    tt = jnp.full((x.shape[0],), t, jnp.float32)
    f = jnp.asarray(TIME_FREQS)
    feats = jnp.concatenate([jnp.sin(tt[:, None] * 1000.0 * f),
                             jnp.cos(tt[:, None] * 1000.0 * f)], -1)
    t_emb = _mm("bd,de->be",
                jax.nn.silu(_mm("bf,fd->bd", feats, top["time_mlp1/w"], mode)),
                top["time_mlp2/w"], mode)
    return x, t_emb


@functools.partial(jax.jit, static_argnums=(3, 4))
def _final(top, x, t_emb, text_tokens: int, mode: str):
    sh, sc = jnp.split(_mm("bd,df->bf", t_emb, top["ada_f/w"], mode), 2, -1)
    x = _modulate(_ln(x, top["ln_f/scale"], top["ln_f/bias"]), sh, sc)
    return _mm("bld,dc->blc", x, top["proj_out/w"], mode)[:, text_tokens:]


def velocity(key, n: Dims, x_lat, cond, t: float, mode: str = "f32",
             devices=None):
    """v(x_t, t) for latents [B, T, 64] and text [B, text_tokens, d].
    With several ``devices`` each block's token rows are split among
    them (every chip computes all keys and values) and joined again."""
    devices = list(devices or [jax.devices()[0]])
    parts = len(devices)
    keys = [jax.device_put(key, d) for d in devices]
    top = _top(keys[0], n)
    x, t_emb = _embed(top, *jax.device_put((x_lat, cond), devices[0]),
                      jnp.float32(t), mode)
    if x.shape[1] % parts:
        raise ValueError(f"{x.shape[1]} rows do not split {parts} ways")
    xs = [jax.device_put(x, d) for d in devices]
    ts = [jax.device_put(t_emb, d) for d in devices]
    for i in range(n.layers):
        outs = [_block_fwd(_block(k, n, jnp.int32(i)), xd, td, n, mode, j,
                           parts)
                for j, (k, xd, td) in enumerate(zip(keys, xs, ts))]
        xs = [_join([jax.device_put(o, d) for o in outs]) for d in devices]
    return _final(top, xs[0], ts[0], n.text_tokens, mode)


def initial_noise(rid: int, tokens: int, dtype: str) -> jax.Array:
    """A served request's starting latents, as the server draws them:
    ``normal(fold_in(PRNGKey(0), rid), [tokens, 64])`` in the served
    dtype."""
    k = jax.random.fold_in(jax.random.PRNGKey(0), rid)
    return jax.random.normal(k, (tokens, LATENT_CHANNELS),
                             jnp.dtype(dtype)).astype(jnp.float32)


def sample(key, n: Dims, x0, cond, steps: int, guidance: float = 1.0,
           mode: str = "f32", devices=None):
    """The Euler sampler from ``x0`` [T, 64] with text ``cond`` [C, d]
    over ``steps`` uniform steps from t = 1.  With guidance the
    conditional and unconditional passes each take half of ``devices``
    (default: the first device), side by side."""
    devices = list(devices or [jax.devices()[0]])
    guided = guidance != 1.0
    if guided and len(devices) > 1:
        half = len(devices) // 2
        dev_c, dev_u = devices[:half], devices[half:2 * half]
    else:
        dev_c = dev_u = devices
    x = jax.device_put(jnp.asarray(x0, jnp.float32)[None], dev_c[0])
    c = jnp.asarray(cond, jnp.float32)[None]
    dt = 1.0 / steps
    for i in range(steps):
        t = 1.0 - i * dt
        v = velocity(key, n, x, c, t, mode, dev_c)
        if guided:
            v_u = velocity(key, n, x, jnp.zeros_like(c), t, mode, dev_u)
            v_u = jax.device_put(v_u, dev_c[0])
            v = v_u + guidance * (v - v_u)
        x = x - dt * v
    return x[0]


def rel_err(got, want, start) -> float:
    """|got - want| / |want - start|: the error against the distance the
    reference moved the latents, on the host in float64."""
    got, want, start = (np.asarray(a, np.float64) for a in (got, want, start))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want - start))
