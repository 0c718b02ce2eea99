"""cogvideox-5b [dit] — the paper's video-generation workload (§5.1)
[arXiv:2408.06072; published transformer config:
https://huggingface.co/THUDM/CogVideoX-5b, ``transformer/config.json``].

42 ``CogVideoXBlock``s at width 3072: 48 heads x 64 with q/k/v and
output biases and a per-head affine LayerNorm on q and k (eps 1e-6),
affine LayerNorms at eps 1e-5 (the blocks' default), a 4x tanh-GELU
feed-forward with biases, and the "expert" adaLN: one modulation of
silu(temb) for the video tokens and another for the text tokens, both
streams through one LayerNorm and one joint attention and feed-forward.
The timestep embedding is 512 wide (sinusoidal features of width 3072,
cos first, no frequency shift); text is 226 T5 tokens 4096 wide; latents
are 16 channels in 2x2 patches (64 a token), 30 x 45 patches a latent
frame at 480x720; 3-D RoPE over (frame, row, col) splits head_dim 64 as
16 / 24 / 24, theta 10000.  About 5.57B parameters.  The 3D-causal VAE,
T5 and the patchifier are stubbed; latent tokens arrive precomputed.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    arch_id="cogvideox-5b",
    family="dit",
    n_layers=42,
    d_model=3072,
    n_heads=48,
    n_kv_heads=48,
    head_dim=64,
    d_ff=12288,
    vocab=0,
    rope="rope3d",
    rope_theta=10000.0,
    qkv_bias=True,
    causal=False,
    act="gelu",
    norm="layernorm",
    block="cogvideox",
    text_tokens=226,
    text_width=4096,
    time_embed_dim=512,
    qk_norm=True,
    proj_bias=True,
    patch_grid=(30, 45),
    rope_axes=(16, 24, 24),
    citation="CogVideoX [18]",
)


def reduced() -> ModelConfig:
    import dataclasses

    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=128, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=512, text_tokens=8, text_width=64, time_embed_dim=64,
        patch_grid=(2, 4), rope_axes=(8, 12, 12))
