"""Model FLOPs of one served sampler step, counted from shapes.

Counts the matmuls of the DiT forward (2 FLOPs a multiply-add), which is
what the chip's matrix units do; norms, softmax and the other
elementwise work are left out, as model FLOPs are.  Only real request
rows count: a padded row is no model work.
"""
from __future__ import annotations

LATENT_CHANNELS = 64
TIME_FEATS = 256


def forward_flops(config: dict, rows: int, latent: int) -> float:
    """One DiT forward over ``rows`` requests of ``latent`` tokens."""
    m = config["model"]
    d, h, hd, ff, n = (m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                       m["n_layers"])
    text = config["text_tokens"]
    length = text + latent
    a = h * hd
    per_block = (2 * d * 6 * d  # adaLN modulation, once a row
                 + length * (2 * d * 3 * a  # q, k, v
                             + 2 * a * d  # output projection
                             + 2 * 2 * d * ff)  # MLP up and down
                 + 2 * 2 * h * length * length * hd)  # scores and p @ v
    outer = (2 * latent * LATENT_CHANNELS * d  # proj_in
             + 2 * text * d * d  # cond_proj
             + 2 * (TIME_FEATS * d + d * d)  # time MLP
             + 2 * d * 2 * d  # final modulation
             + 2 * length * d * LATENT_CHANNELS)  # proj_out
    return float(rows * (n * per_block + outer))


def step_flops(config: dict, rows: int, latent: int) -> float:
    """One sampler step: one forward, two under classifier-free guidance."""
    branches = 2 if config["sampler"].get("guidance_scale", 1.0) != 1.0 else 1
    return branches * forward_flops(config, rows, latent)
