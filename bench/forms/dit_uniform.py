"""The repo's uniform adaLN-zero DiT, as ``repro.models.dit`` builds it.

[text ; latent] tokens, uniform blocks (LayerNorm, modulate, 1-D RoPE
attention over the joint positions, tanh-GELU MLP, gated residuals), a
modulated final LayerNorm and projection.  A config names this form with
``"form": "dit_uniform"``; the harness finds this file by that name.

It gives what the harness asks of a form: ``PROGRAM_KEYS`` and
``program_sizes`` (what the program must be built as for this maths to
be its reference), ``Dims.of``, ``INIT``, the float32 ``sample`` (with
``mode="fp8"`` for the control), ``forward_flops`` and ``part_flops``.
"""
from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R

# what this module implements; the program's config must say the same
PROGRAM_KEYS = {"act": "gelu", "norm": "layernorm", "rope": "rope",
              "rope_theta": 10000.0, "rope_pct": 1.0, "qkv_bias": False,
              "causal": False}
LATENT_CHANNELS = 64  # the program's proj_in / proj_out width
TEXT_TOKENS = 256  # where the program splits [text ; latent]
TIME_FEATS = 256
ROPE_THETA = 10000.0
LN_EPS = 1e-5
# The program takes its timestep frequencies from row 0 of a sinusoid
# table, which is sin(0) = 0 everywhere: its time features are the
# constant [0, ..., 0, 1, ..., 1] whatever t is.  The reference computes
# the model as built (PERF.md, Open questions).
TIME_FREQS = np.zeros(TIME_FEATS // 2, np.float32)
# (leaves that start at zero, residual outputs), by path suffix
INIT = (("ada/w", "ada_f/w", "proj_out/w"), ("attn/wo/w", "mlp/wo/w"))


def program_sizes(config: dict) -> dict:
    """The text and latent sizes the program takes at this config."""
    return {"text_tokens": TEXT_TOKENS,
            "text_width": config["model"]["d_model"],
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

    @classmethod
    def of(cls, config: dict) -> "Dims":
        m = config["model"]
        return cls(m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                   m["n_layers"], config["text_tokens"],
                   config["text_width"], config["latent_channels"],
                   m["dtype"])


def top_shapes(n: Dims) -> tuple:
    d = n.d
    return (("proj_in/w", (n.latent_channels, d)),
            ("cond_proj/w", (n.text_width, d)),
            ("time_mlp1/w", (TIME_FEATS, d)), ("time_mlp2/w", (d, d)),
            ("ln_f/scale", (d,)), ("ln_f/bias", (d,)),
            ("ada_f/w", (d, 2 * d)), ("proj_out/w", (d, n.latent_channels)))


def block_shapes(n: Dims) -> tuple:
    d, a = n.d, n.heads * n.head_dim
    return (("ln_attn/scale", (d,)), ("ln_attn/bias", (d,)),
            ("attn/wq/w", (d, a)), ("attn/wk/w", (d, a)),
            ("attn/wv/w", (d, a)), ("attn/wo/w", (a, d)),
            ("ln_mlp/scale", (d,)), ("ln_mlp/bias", (d,)),
            ("mlp/wi_up/w", (d, n.d_ff)), ("mlp/wo/w", (n.d_ff, d)),
            ("ada/w", (d, 6 * d)))


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


def _attention(h, hq, q0: int, w, n: Dims, mode: str):
    """Attention of the rows ``hq`` (starting at position ``q0``) to every
    row of ``h``."""
    b, length, _ = h.shape
    lq = hq.shape[1]
    q = R.mm("bld,da->bla", hq, w["attn/wq/w"], mode)
    k = R.mm("bld,da->bla", h, w["attn/wk/w"], mode)
    v = R.mm("bld,da->bla", h, w["attn/wv/w"], mode)
    q = _rope(q.reshape(b, lq, n.heads, n.head_dim), q0 + jnp.arange(lq))
    k = _rope(k.reshape(b, length, n.heads, n.head_dim), jnp.arange(length))
    v = v.reshape(b, length, n.heads, n.head_dim)
    return R.mm("bla,ad->bld", R.blocked_attention(q, k, v, mode),
                w["attn/wo/w"], mode)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def block(w, x, t_emb, n: Dims, mode: str, part: int = 0, parts: int = 1):
    """One block; returns the rows of token slice ``part`` of ``parts``
    (every row's keys and values are computed, so the slices of one
    block can run on different chips)."""
    mod = R.mm("bd,df->bf", t_emb, w["ada/w"], mode)
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
    h = _modulate(_ln(x, w["ln_attn/scale"], w["ln_attn/bias"]), sh1, sc1)
    rows = x.shape[1] // parts
    q0 = part * rows
    x = x[:, q0:q0 + rows]
    x = x + g1[:, None] * _attention(h, h[:, q0:q0 + rows], q0, w, n, mode)
    h = _modulate(_ln(x, w["ln_mlp/scale"], w["ln_mlp/bias"]), sh2, sc2)
    u = jax.nn.gelu(R.mm("bld,df->blf", h, w["mlp/wi_up/w"], mode),
                    approximate=True)
    return x + g2[:, None] * R.mm("blf,fd->bld", u, w["mlp/wo/w"], mode)


@functools.partial(jax.jit, static_argnums=(4,))
def embed(top, x_lat, cond, t, mode: str):
    x = jnp.concatenate([R.mm("bcd,de->bce", cond, top["cond_proj/w"], mode),
                         R.mm("btc,cd->btd", x_lat, top["proj_in/w"], mode)],
                        axis=1)
    tt = jnp.full((x.shape[0],), t, jnp.float32)
    f = jnp.asarray(TIME_FREQS)
    feats = jnp.concatenate([jnp.sin(tt[:, None] * 1000.0 * f),
                             jnp.cos(tt[:, None] * 1000.0 * f)], -1)
    t_emb = R.mm("bd,de->be",
                 jax.nn.silu(R.mm("bf,fd->bd", feats, top["time_mlp1/w"],
                                  mode)),
                 top["time_mlp2/w"], mode)
    return x, t_emb


@functools.partial(jax.jit, static_argnums=(3, 4))
def final(top, x, t_emb, text_tokens: int, mode: str):
    sh, sc = jnp.split(R.mm("bd,df->bf", t_emb, top["ada_f/w"], mode), 2, -1)
    x = _modulate(_ln(x, top["ln_f/scale"], top["ln_f/bias"]), sh, sc)
    return R.mm("bld,dc->blc", x, top["proj_out/w"], mode)[:, text_tokens:]


def sample(key, n: Dims, x0, cond, steps: int, guidance: float = 1.0,
           mode: str = "f32", devices=None):
    """``reference.sample`` of this form."""
    return R.sample(sys.modules[__name__], key, n, x0, cond, steps,
                    guidance, mode, devices)


def part_flops(config: dict, rows: int, latent: int) -> dict[str, float]:
    """Model FLOPs (matmuls, 2 a multiply-add, real rows only) of one
    forward's blocks by the program's named scopes: ``attn`` the scores
    and p @ v, ``mlp`` the MLP up and down, ``proj`` the q, k, v (scope
    ``qkv``) and output (scope ``attn_out``) projections.  The adaLN
    modulation is in no part."""
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
    tokens: the blocks' parts, their adaLN modulation (once a row) and
    the layers outside the blocks."""
    m = config["model"]
    d, n = m["d_model"], m["n_layers"]
    text, width = config["text_tokens"], config["text_width"]
    channels = config["latent_channels"]
    length = text + latent
    outer = (2 * latent * channels * d  # proj_in
             + 2 * text * width * d  # cond_proj
             + 2 * (TIME_FEATS * d + d * d)  # time MLP
             + 2 * d * 2 * d  # final modulation
             + 2 * length * d * channels)  # proj_out
    return (sum(part_flops(config, rows, latent).values())
            + float(rows * (n * 2 * d * 6 * d + outer)))
