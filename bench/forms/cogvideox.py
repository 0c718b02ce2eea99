"""CogVideoX-5B's transformer, as published (diffusers
``CogVideoXTransformer3DModel``, ``transformer/config.json`` of
https://huggingface.co/THUDM/CogVideoX-5b; arXiv:2408.06072).

[text ; video] tokens: the text (T5 embeddings) through ``text_proj``
4096 -> d and the video's 2x2 patches through the patch embedding (a
linear map of the packed 64 channels), both with bias.  The timestep's
sinusoidal features of width d (cos first, no frequency shift) of
t x 1000 go through a linear-silu-linear embedding of width 512.  Each
``CogVideoXBlock``: one affine LayerNorm (eps 1e-5) of both streams, the
video tokens modulated by (shift, scale) and the text tokens by their
own, both from one linear map of silu(temb) ("expert" adaLN); joint
attention with biased q, k, v and output projections, a per-head affine
LayerNorm of q and k (eps 1e-6) and 3-D RoPE on the video tokens only
(head_dim split 16 / 24 / 24 over frame, row and column, interleaved
pairs, theta 10000); each stream adds its own gated residual; then the
same for the tanh-GELU feed-forward (4x, with biases).  At the end a
LayerNorm of the joint sequence, then on the video tokens an
AdaLayerNorm (shift, scale of silu(temb)) and ``proj_out`` d -> 64.

Departures from the published model (also the config's ``assumed``):
the repo's flow-matching Euler sampler with guidance against all-zero
text embeddings replaces CogVideoX's v-prediction DDIM/DPM scheduler and
T5(""); the time features take t x 1000 for t in (0, 1]; latents arrive
packed and the VAE is stubbed; weights are seeded, not the checkpoint's.

A config names this form with ``"form": "cogvideox"``.  It gives what
the harness asks of a form: ``PROGRAM_KEYS`` and ``program_sizes``,
``Dims.of``, ``INIT``, the float32 ``sample`` (``mode="fp8"`` the
control), ``forward_flops`` and ``part_flops``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys

import jax
import jax.numpy as jnp

from bench import reference as R

# what this module implements; the program's config must say the same
PROGRAM_KEYS = {"block": "cogvideox", "act": "gelu", "norm": "layernorm",
                "rope": "rope3d", "rope_theta": 10000.0, "qkv_bias": True,
                "proj_bias": True, "qk_norm": True, "causal": False,
                "time_embed_dim": 512, "patch_grid": (30, 45),
                "rope_axes": (16, 24, 24)}
LATENT_CHANNELS = 64  # 16 VAE channels in 2x2 patches
TEXT_TOKENS = 226  # max_text_seq_length
TEXT_WIDTH = 4096  # text_embed_dim
LN_EPS = 1e-5  # norm_eps
QK_EPS = 1e-6  # the attention's q/k LayerNorm
# (leaves that start at zero, residual outputs), by path suffix: the
# published model's modulations and output start where the seeded ones
# must not, or a fresh stack is the identity
INIT = (("ada_attn/w", "ada_mlp/w", "ada_f/w", "proj_out/w"),
        ("attn/wo/w", "mlp/wo/w"))


def program_sizes(config: dict) -> dict:
    """The text and latent sizes the program takes at this config."""
    return {"text_tokens": TEXT_TOKENS, "text_width": TEXT_WIDTH,
            "latent_channels": LATENT_CHANNELS}


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    head_dim: int
    d_ff: int
    layers: int
    text_tokens: int
    text_width: int
    latent_channels: int
    dtype: str  # the served dtype the weights are rounded to
    time_embed_dim: int = PROGRAM_KEYS["time_embed_dim"]
    grid: tuple = PROGRAM_KEYS["patch_grid"]  # (rows, cols) a frame
    rope_axes: tuple = PROGRAM_KEYS["rope_axes"]  # (frame, row, col)

    @classmethod
    def of(cls, config: dict) -> "Dims":
        m = config["model"]
        return cls(m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                   m["n_layers"], config["text_tokens"],
                   config["text_width"], config["latent_channels"],
                   m["dtype"])


def top_shapes(n: Dims) -> tuple:
    d, te, c = n.d, n.time_embed_dim, n.latent_channels
    return (("proj_in/w", (c, d)), ("proj_in/b", (d,)),
            ("cond_proj/w", (n.text_width, d)), ("cond_proj/b", (d,)),
            ("time_mlp1/w", (d, te)), ("time_mlp1/b", (te,)),
            ("time_mlp2/w", (te, te)), ("time_mlp2/b", (te,)),
            ("ln_f/scale", (d,)), ("ln_f/bias", (d,)),
            ("ln_out/scale", (d,)), ("ln_out/bias", (d,)),
            ("ada_f/w", (te, 2 * d)), ("ada_f/b", (2 * d,)),
            ("proj_out/w", (d, c)), ("proj_out/b", (c,)))


def block_shapes(n: Dims) -> tuple:
    d, a, hd, te = n.d, n.heads * n.head_dim, n.head_dim, n.time_embed_dim
    return (("ln_attn/scale", (d,)), ("ln_attn/bias", (d,)),
            ("attn/wq/w", (d, a)), ("attn/wq/b", (a,)),
            ("attn/wk/w", (d, a)), ("attn/wk/b", (a,)),
            ("attn/wv/w", (d, a)), ("attn/wv/b", (a,)),
            ("attn/wo/w", (a, d)), ("attn/wo/b", (d,)),
            ("attn/q_norm/scale", (hd,)), ("attn/q_norm/bias", (hd,)),
            ("attn/k_norm/scale", (hd,)), ("attn/k_norm/bias", (hd,)),
            ("ln_mlp/scale", (d,)), ("ln_mlp/bias", (d,)),
            ("mlp/wi_up/w", (d, n.d_ff)), ("mlp/wi_up/b", (n.d_ff,)),
            ("mlp/wo/w", (n.d_ff, d)), ("mlp/wo/b", (d,)),
            ("ada_attn/w", (te, 6 * d)), ("ada_attn/b", (6 * d,)),
            ("ada_mlp/w", (te, 6 * d)), ("ada_mlp/b", (6 * d,)))


def _ln(x, scale, bias, eps=LN_EPS):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _lin(x, w, name: str, mode: str):
    """``x @ w[name/w] + w[name/b]`` over the last axis."""
    return R.mm("...d,df->...f", x, w[name + "/w"], mode) + w[name + "/b"]


def _axis_rotary(dim: int, pos, theta: float):
    """diffusers' ``get_1d_rotary_pos_embed(use_real=True,
    repeat_interleave_real=True)``: cos and sin [len(pos), dim], each
    frequency repeated for the two members of its pair."""
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.outer(pos.astype(jnp.float32), freqs)
    return (jnp.repeat(jnp.cos(ang), 2, axis=1),
            jnp.repeat(jnp.sin(ang), 2, axis=1))


def rotary_tables(n: Dims, latent: int):
    """cos and sin [text + latent, head_dim] as diffusers'
    ``get_3d_rotary_pos_embed`` builds them for the video tokens, frame
    by frame, row by row; the text rows do not rotate (cos 1, sin 0)."""
    rows, cols = n.grid
    frames = latent // (rows * cols)
    if frames * rows * cols != latent:
        raise ValueError(f"{latent} latent tokens are not whole frames of "
                         f"{rows} x {cols} patches")
    theta = PROGRAM_KEYS["rope_theta"]
    parts = [_axis_rotary(w, jnp.arange(k), theta)
             for w, k in zip(n.rope_axes, (frames, rows, cols))]
    shape = (frames, rows, cols)

    def grid(i):  # table i of (cos, sin) on the (frame, row, col) grid
        ct, ch, cw = (p[i] for p in parts)
        return jnp.concatenate(
            [jnp.broadcast_to(ct[:, None, None], shape + ct.shape[-1:]),
             jnp.broadcast_to(ch[None, :, None], shape + ch.shape[-1:]),
             jnp.broadcast_to(cw[None, None, :], shape + cw.shape[-1:])],
            axis=-1).reshape(latent, n.head_dim)

    text = n.text_tokens
    return (jnp.concatenate([jnp.ones((text, n.head_dim)), grid(0)]),
            jnp.concatenate([jnp.zeros((text, n.head_dim)), grid(1)]))


def apply_rotary(x, cos, sin):
    """diffusers' ``apply_rotary_emb(use_real_unbind_dim=-1)`` of x
    [B, L, H, hd] with tables [L, hd]."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    rotated = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(x.shape)
    return x * cos[None, :, None] + rotated * sin[None, :, None]


def _streams(text_part, video_part, text: int, length: int):
    """Per-row [B, length, d] of a per-stream [B, d] value: the text's on
    the first ``text`` rows, the video's on the rest."""
    b, d = text_part.shape
    return jnp.concatenate(
        [jnp.broadcast_to(text_part[:, None], (b, text, d)),
         jnp.broadcast_to(video_part[:, None], (b, length - text, d))], 1)


def _norm_zero(x, w, name: str, emb, text: int, mode: str):
    """``CogVideoXLayerNormZero``: the shared LayerNorm of every row of x,
    each stream modulated by its own (shift, scale); with the per-row
    gate."""
    sh, sc, g, esh, esc, eg = jnp.split(
        _lin(jax.nn.silu(emb), w, f"ada_{name}", mode), 6, axis=-1)
    length = x.shape[1]
    h = _ln(x, w[f"ln_{name}/scale"], w[f"ln_{name}/bias"])
    h = h * (1.0 + _streams(esc, sc, text, length)) + _streams(
        esh, sh, text, length)
    return h, _streams(eg, g, text, length)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def block(w, x, t_emb, n: Dims, mode: str, part: int = 0, parts: int = 1):
    """One CogVideoXBlock; returns the rows of token slice ``part`` of
    ``parts`` (every row's keys and values are computed, so the slices
    of one block can run on different chips)."""
    b, length, _ = x.shape
    text = n.text_tokens
    rows = length // parts
    q0 = part * rows
    sl = slice(q0, q0 + rows)
    h, gate = _norm_zero(x, w, "attn", t_emb, text, mode)
    cos, sin = rotary_tables(n, length - text)

    def heads(y, name):
        return _lin(y, w, f"attn/{name}", mode).reshape(
            *y.shape[:2], n.heads, n.head_dim)

    def qk(y, name, s):
        y = _ln(heads(y, f"w{name}"), w[f"attn/{name}_norm/scale"],
                w[f"attn/{name}_norm/bias"], QK_EPS)
        return apply_rotary(y, cos[s], sin[s])

    q = qk(h[:, sl], "q", sl)
    k = qk(h, "k", slice(None))
    v = heads(h, "wv")
    o = _lin(R.blocked_attention(q, k, v, mode), w, "attn/wo", mode)
    x = x[:, sl] + gate[:, sl] * o
    # the feed-forward's norm is per row, so the slice takes it alone
    sh, sc, g, esh, esc, eg = jnp.split(
        _lin(jax.nn.silu(t_emb), w, "ada_mlp", mode), 6, axis=-1)
    hm = _ln(x, w["ln_mlp/scale"], w["ln_mlp/bias"])
    hm = (hm * (1.0 + _streams(esc, sc, text, length)[:, sl])
          + _streams(esh, sh, text, length)[:, sl])
    u = jax.nn.gelu(_lin(hm, w, "mlp/wi_up", mode), approximate=True)
    return x + _streams(eg, g, text, length)[:, sl] * _lin(u, w, "mlp/wo",
                                                           mode)


@functools.partial(jax.jit, static_argnums=(4,))
def embed(top, x_lat, cond, t, mode: str):
    """[text ; video] tokens and the timestep embedding temb."""
    x = jnp.concatenate([_lin(cond, top, "cond_proj", mode),
                         _lin(x_lat, top, "proj_in", mode)], axis=1)
    half = top["cond_proj/w"].shape[1] // 2
    freqs = jnp.exp(-math.log(10000.0)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.full((x.shape[0], 1), t * 1000.0, jnp.float32) * freqs
    feats = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)
    emb = _lin(jax.nn.silu(_lin(feats, top, "time_mlp1", mode)), top,
               "time_mlp2", mode)
    return x, emb


@functools.partial(jax.jit, static_argnums=(3, 4))
def final(top, x, t_emb, text_tokens: int, mode: str):
    """``norm_final`` of the joint rows, then on the video rows the
    AdaLayerNorm and ``proj_out``."""
    x = _ln(x, top["ln_f/scale"], top["ln_f/bias"])[:, text_tokens:]
    sh, sc = jnp.split(_lin(jax.nn.silu(t_emb), top, "ada_f", mode), 2, -1)
    x = (_ln(x, top["ln_out/scale"], top["ln_out/bias"]) * (1.0 + sc[:, None])
         + sh[:, None])
    return _lin(x, top, "proj_out", mode)


def sample(key, n: Dims, x0, cond, steps: int, guidance: float = 1.0,
           mode: str = "f32", devices=None):
    """``reference.sample`` of this form."""
    return R.sample(sys.modules[__name__], key, n, x0, cond, steps,
                    guidance, mode, devices)


def part_flops(config: dict, rows: int, latent: int) -> dict[str, float]:
    """Model FLOPs (matmuls, 2 a multiply-add, real rows only) of one
    forward's blocks by the program's named scopes: ``attn`` the scores
    and p @ v, ``mlp`` the feed-forward up and down, ``proj`` the q, k,
    v (scope ``qkv``) and output (scope ``attn_out``) projections.  The
    expert adaLN's modulation (scope ``modulate``) is in no part."""
    m = config["model"]
    d, h, hd, ff, n = (m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                       m["n_layers"])
    length = config["text_tokens"] + latent
    a = h * hd
    per_row_block = {
        "attn": 4 * h * length * length * hd,
        "mlp": 4 * length * d * ff,
        "proj": length * (6 * d * a + 2 * a * d),
    }
    return {k: float(rows * n * v) for k, v in per_row_block.items()}


def forward_flops(config: dict, rows: int, latent: int) -> float:
    """Model FLOPs of one forward over ``rows`` requests of ``latent``
    tokens, as the program computes it: the blocks' parts, their two
    modulations (once a row), and the layers outside the blocks, its
    final norm and projection over every row."""
    m = config["model"]
    d, n = m["d_model"], m["n_layers"]
    te = PROGRAM_KEYS["time_embed_dim"]
    text, width = config["text_tokens"], config["text_width"]
    channels = config["latent_channels"]
    length = text + latent
    outer = (2 * latent * channels * d  # patch embedding
             + 2 * text * width * d  # text projection
             + 2 * (d * te + te * te)  # time embedding
             + 2 * te * 2 * d  # final modulation
             + 2 * length * d * channels)  # proj_out
    return (sum(part_flops(config, rows, latent).values())
            + float(rows * (n * 2 * 2 * te * 6 * d + outer)))
