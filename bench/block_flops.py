"""Model FLOPs of the parts of the DiT block, one served sampler step.

The same count as ``bench.flops.forward_flops`` (matmuls only, 2 FLOPs a
multiply-add, real request rows only), split by the program's named
scopes: ``attn`` the scores and p @ v, ``mlp`` the MLP up and down,
``proj`` the q, k, v (scope ``qkv``) and output (scope ``attn_out``)
projections.  The adaLN modulation and the layers outside the blocks
are in no part.
"""
from __future__ import annotations

PARTS = ("attn", "mlp", "proj")


def block_flops(config: dict, rows: int, latent: int) -> dict[str, float]:
    """Each part's FLOPs over every block, one sampler step of ``rows``
    requests of ``latent`` tokens (two forwards under guidance)."""
    m = config["model"]
    d, h, hd, ff, n = (m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                       m["n_layers"])
    length = config["text_tokens"] + latent
    a = h * hd
    branches = 2 if config["sampler"].get("guidance_scale", 1.0) != 1.0 else 1
    per_row_block = {
        "attn": 4 * h * length * length * hd,
        "mlp": 4 * length * d * ff,
        "proj": length * (6 * d * a + 2 * a * d),
    }
    return {k: float(branches * rows * n * v)
            for k, v in per_row_block.items()}
