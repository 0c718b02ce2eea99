"""Ulysses Attention transforms (paper §2.2) over a logical Ulysses group.

The forward transform runs the three all-to-alls on Q, K, V: scatter the
head dimension (H -> H/P_u) and gather the sequence dimension
(L/P -> P_u * L/P) within each Ulysses group.  The inverse transform is the
fourth all-to-all restoring O to [B, L/P, H, D].

Gathered chunks are ordered by source ulysses coordinate; because group
members are not adjacent in the global sequence when the group spans the
slow axis, the transforms also return global *position arrays* used for
exact causal/window masking downstream.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .collectives import GroupLayout, monolithic_all_to_all, ungroup_all_to_all

HEAD_AXIS = 2  # [B, L, H, D]
SEQ_AXIS = 1


class Gathered(NamedTuple):
    q: jax.Array  # [B, P_u * Ls, Hq / P_u, D]
    k: jax.Array  # [B, P_u * Ls, Hkv / P_u, D]
    v: jax.Array
    q_pos: jax.Array  # [P_u * Ls] global positions of the gathered sequence


def group_positions(layout: GroupLayout, shard_len: int, ring_r) -> jax.Array:
    """Global positions of the sequence gathered by the Ulysses group whose
    ring coordinate is ``ring_r`` (traced ok), ordered by source u."""
    us = jnp.arange(layout.p_ulysses)
    if layout.ulysses_outer:
        ranks = us * layout.p_ring + ring_r
    else:
        ranks = ring_r * layout.p_ulysses + us
    return (ranks[:, None] * shard_len + jnp.arange(shard_len)[None, :]).reshape(-1)


def gather_qkv(
    q: jax.Array, k: jax.Array, v: jax.Array, layout: GroupLayout,
    *, backend: str = "xla", wire_dtype: str | None = None,
) -> Gathered:
    """The first three all-to-alls of Ulysses Attention.  ``wire_dtype``
    compresses the inter-machine leg when the layout is hierarchical
    (``layout.u_groups > 1``, DESIGN.md §8.2); ignored otherwise."""
    shard_len = q.shape[SEQ_AXIS]

    def fwd(x):
        stacked = monolithic_all_to_all(x, layout, split_axis=HEAD_AXIS,
                                        backend=backend, wire_dtype=wire_dtype)
        # [P_u, B, Ls, h, D] -> [B, P_u * Ls, h, D], source-u order
        p_u, b, ls, h, d = stacked.shape
        return jnp.moveaxis(stacked, 0, 1).reshape(b, p_u * ls, h, d)

    _, my_r = layout.my_coords()
    return Gathered(
        q=fwd(q), k=fwd(k), v=fwd(v), q_pos=group_positions(layout, shard_len, my_r)
    )


def scatter_o(o: jax.Array, layout: GroupLayout, *, backend: str = "xla",
              wire_dtype: str | None = None) -> jax.Array:
    """The fourth all-to-all: restore O from [B, P_u*Ls, H/P_u, D] to the
    original [B, Ls, H, D] sequence sharding."""
    p_u = layout.p_ulysses
    b, lg, h, d = o.shape
    stacked = o.reshape(b, p_u, lg // p_u, h, d).transpose(1, 0, 2, 3, 4)
    return ungroup_all_to_all(stacked, layout, concat_axis=HEAD_AXIS,
                              backend=backend, wire_dtype=wire_dtype)
