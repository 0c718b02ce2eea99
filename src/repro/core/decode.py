"""Distributed decode attention over a sequence-sharded KV cache.

Decode shapes (one new token against a long cached context) invert the SP
problem: Q is a single position, the KV cache is what is sharded.  Each SP
shard attends the replicated Q against its local cache slice, producing an
online-softmax partial ``(O', l, m)``; partials are combined with one tiny
``pmax``/``psum`` pair over the SP axes (the distributed form of the
Appendix-C merge — communication is O(B·H·D), independent of context
length).  The new token's KV is written into the shard that owns position
``cur_index``.

This is the flash-decoding analogue of the paper's schedule: all heavy
tensors stay put; only scalar-scale statistics cross the network.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .collectives import flat_rank
from .softmax import MaskSpec, attend_partial
from .strategy import SPConfig


def _local_decode(
    q, k_cache, v_cache, new_k, new_v, cur_index, *, sp_axes, shard_len, scale, window
):
    """Per-device body: write new KV into my slice if I own the position,
    attend q against my slice, merge partials across the SP group."""
    my_rank = flat_rank(sp_axes)
    local_start = my_rank * shard_len
    owns = (cur_index >= local_start) & (cur_index < local_start + shard_len)
    idx = jnp.clip(cur_index - local_start, 0, shard_len - 1)

    def write(cache, new):
        updated = lax.dynamic_update_slice_in_dim(cache, new, idx, axis=1)
        return jnp.where(owns, updated, cache)

    k_cache = write(k_cache, new_k)
    v_cache = write(v_cache, new_v)

    pos = local_start + jnp.arange(shard_len)
    valid = pos <= cur_index
    if window is not None:
        valid &= pos > cur_index - window
    part = attend_partial(
        q, k_cache, v_cache, scale=scale, mask=MaskSpec(valid_k=valid)
    )
    # distributed Appendix-C merge: one pmax + two psums of [B, H, 1]-sized stats
    m_g = lax.pmax(part.m, sp_axes)
    safe = jnp.where(jnp.isneginf(part.m) & jnp.isneginf(m_g), 0.0, part.m - m_g)
    a = jnp.exp(safe)
    l_g = lax.psum(part.l * a, sp_axes)
    o_g = lax.psum(part.o * jnp.swapaxes(a, 1, 2)[..., None], sp_axes)
    l_sw = jnp.swapaxes(l_g, 1, 2)[..., None]  # [B, Lq, Hq, 1]
    o = o_g / jnp.where(l_sw == 0.0, 1.0, l_sw)
    return o.astype(q.dtype), k_cache, v_cache


def decode_attention(
    q: jax.Array,  # [B, 1, Hq, D] the new token's query
    k_cache: jax.Array,  # [B, L_max, Hkv, D] sharded over cfg.sp_axes on L
    v_cache: jax.Array,
    new_k: jax.Array,  # [B, 1, Hkv, D]
    new_v: jax.Array,
    cur_index: jax.Array,  # [] int32: position being decoded
    *,
    mesh: jax.sharding.Mesh,
    cfg: SPConfig,
    scale: float | None = None,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attention output [B, 1, Hq, D], updated k_cache, v_cache)."""
    sp = math.prod(mesh.shape[a] for a in cfg.sp_axes)
    ba = cfg.batch_axes
    shard_len = k_cache.shape[1] // sp
    if scale is None:
        scale = q.shape[-1] ** -0.5

    qspec = P(ba, None, None, None)
    cspec = P(ba, cfg.sp_axes, None, None)
    body = partial(
        _local_decode,
        sp_axes=cfg.sp_axes,
        shard_len=shard_len,
        scale=scale,
        window=window,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qspec, cspec, cspec, qspec, qspec, P()),
        out_specs=(qspec, cspec, cspec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, new_k, new_v, cur_index)
