"""Collective toolkit for SwiftFusion's SP schedules on TPU meshes.

The paper implements its communication with one-sided NVSHMEM put/get so
that (a) no per-transfer sender/receiver rendezvous happens and (b) no SM
cycles are burnt on communication kernels.  The TPU-idiomatic equivalent
lives in ``repro.comm`` (DESIGN.md §8): channels whose ``put`` is a
``lax.ppermute`` — lowered to ``collective-permute-start/done`` pairs
executed by the ICI DMA engines (no core cycles), with XLA's latency-hiding
scheduler hoisting the ``start`` above independent compute — precisely the
overlap NVSHMEM gives the paper.  Every schedule is therefore built from
channel puts over a *flattened* SP axis, with the paper's logical
(P_u × P_r) factorisation expressed as plain rank arithmetic.  This module
owns the layout bookkeeping (GroupLayout) and the all-to-all entry points;
the staged transfer programs themselves are ``repro.comm.stream``'s.  The
entry points, like the ring and torus hops in ``ring.py`` and
``torus.py``, run under the named scope ``sp_comm``: in a device trace
the SP exchange's ops carry it in their ``tf_op`` path.

Logical layout (see planner.py):
  flat rank p in [0, P_u * P_r) over the mesh SP axes (major axis first).
  SwiftFusion (ulysses_outer=True):  u = p // P_r,  r = p %  P_r
      → Ulysses groups span the slow outer (pod) boundary, Ring groups are
        contiguous inside a pod.
  USP       (ulysses_outer=False):   u = p %  P_u,  r = p // P_u
      → Ring groups span pods, Ulysses groups stay inside a pod.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ..comm import (hier_all_to_all, hier_ungroup, staged_all_to_all,
                    staged_ungroup)

AxisNames = tuple[str, ...]


def flat_axis_size(mesh: jax.sharding.Mesh | None, axes: AxisNames) -> int:
    if mesh is None:  # inside shard_map: use psum-of-ones trick? callers pass mesh
        raise ValueError("mesh required")
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s


def flat_rank(axes: AxisNames) -> jax.Array:
    """Flattened rank over (possibly multiple) named mesh axes, major-first."""
    return lax.axis_index(axes)


@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """(P_u × P_r) logical factorisation of a flattened SP axis."""

    axes: AxisNames
    p_ulysses: int
    p_ring: int
    ulysses_outer: bool  # True = SwiftFusion/TAS; False = USP
    # Hierarchical a2a factorisation (DESIGN.md §8.2): number of machine
    # sub-groups each Ulysses group is split into.  u_groups == 1 is the
    # flat (monolithic or staged) a2a; u_groups == N decomposes every
    # Ulysses transform into an intra-machine exchange followed by
    # staged inter-machine hops.  Only meaningful with ulysses_outer
    # (the u-blocks must be machine-contiguous); resolve_layout enforces
    # the divisibility conditions.
    u_groups: int = 1

    @property
    def size(self) -> int:
        return self.p_ulysses * self.p_ring

    @property
    def u_group_size(self) -> int:
        """m_u: Ulysses-group members per machine sub-group."""
        return self.p_ulysses // self.u_groups

    # -- static (python int) coordinates, used to build perm tables --------
    def coords(self, p: int) -> tuple[int, int]:
        if self.ulysses_outer:
            return p // self.p_ring, p % self.p_ring
        return p % self.p_ulysses, p // self.p_ulysses

    def rank(self, u: int, r: int) -> int:
        if self.ulysses_outer:
            return u * self.p_ring + r
        return r * self.p_ulysses + u

    # -- traced coordinates, used inside shard_map bodies -------------------
    def my_coords(self) -> tuple[jax.Array, jax.Array]:
        p = flat_rank(self.axes)
        if self.ulysses_outer:
            return p // self.p_ring, p % self.p_ring
        return p % self.p_ulysses, p // self.p_ulysses

    # -- permutation tables --------------------------------------------------
    def ring_perm(self, shift: int = 1) -> list[tuple[int, int]]:
        """Rotate by ``shift`` inside each Ring group (same u)."""
        out = []
        for u in range(self.p_ulysses):
            for r in range(self.p_ring):
                out.append((self.rank(u, r), self.rank(u, (r + shift) % self.p_ring)))
        return out

    def ulysses_stage_perm(self, k: int) -> list[tuple[int, int]]:
        """Stage ``k`` of the decomposed all-to-all: u sends to (u + k) % P_u
        inside each Ulysses group (same r).  §4.3 'Breakdown of All-to-All'."""
        out = []
        for u in range(self.p_ulysses):
            for r in range(self.p_ring):
                out.append(
                    (self.rank(u, r), self.rank((u + k) % self.p_ulysses, r))
                )
        return out

    def ulysses_intra_stage_perm(self, j: int) -> list[tuple[int, int]]:
        """Stage ``j`` of the hierarchical a2a's *fast leg*: distance-j
        rotation of the local coordinate u_lo = u % m_u inside each machine
        sub-group (same u_hi, same r).  With u_groups == N and
        ulysses_outer, every (u_hi, r) block is exactly one machine, so
        this perm never crosses the slow boundary."""
        g, m_u = self.u_groups, self.u_group_size
        out = []
        for hi in range(g):
            for lo in range(m_u):
                for r in range(self.p_ring):
                    out.append((
                        self.rank(hi * m_u + lo, r),
                        self.rank(hi * m_u + (lo + j) % m_u, r),
                    ))
        return out

    def ulysses_inter_stage_perm(self, k: int) -> list[tuple[int, int]]:
        """Stage ``k`` of the hierarchical a2a's *slow leg*: distance-k
        rotation of the machine coordinate u_hi = u // m_u (same u_lo,
        same r) — the only leg that touches the inter-machine wire."""
        g, m_u = self.u_groups, self.u_group_size
        out = []
        for hi in range(g):
            for lo in range(m_u):
                for r in range(self.p_ring):
                    out.append((
                        self.rank(hi * m_u + lo, r),
                        self.rank(((hi + k) % g) * m_u + lo, r),
                    ))
        return out

    def seq_offset_of_rank(self, shard_len: int) -> jax.Array:
        """Global sequence offset of *this* device's original shard."""
        return flat_rank(self.axes) * shard_len

    def ulysses_group_offsets(self, shard_len: int) -> jax.Array:
        """Global seq offsets of the shards gathered from my Ulysses group,
        ordered by source ulysses-coordinate u' = 0..P_u-1.  Traced."""
        _, r = self.my_coords()
        us = jnp.arange(self.p_ulysses)
        if self.ulysses_outer:
            ranks = us * self.p_ring + r
        else:
            ranks = r * self.p_ulysses + us
        return ranks * shard_len


# ---------------------------------------------------------------------------
# Grouped all-to-all via staged channel puts (the one-sided decomposition);
# the transfer programs live in repro.comm.stream, this is the core-facing
# entry point.
# ---------------------------------------------------------------------------

@jax.named_scope("sp_comm")
def grouped_all_to_all(
    x: jax.Array,
    layout: GroupLayout,
    *,
    split_axis: int,
    stack_axis: int = 0,
    backend: str = "xla",
    wire_dtype: str | None = None,
) -> jax.Array:
    """All-to-all restricted to Ulysses groups of ``layout``.

    Splits ``x`` into P_u equal chunks along ``split_axis``; chunk j is
    delivered to ulysses-peer j.  Returns the received chunks stacked on a
    new leading axis ordered by *source* ulysses coordinate:
    ``out[j] = chunk (destined for me) from peer with u = j``.

    Implemented as P_u - 1 one-sided channel stages (comm.stream).  The
    diagonal chunk (j == my u) is **stationary** — the paper's §4.3
    observation — and never moves.  With ``layout.u_groups > 1`` the
    exchange runs the hierarchical two-level program instead (DESIGN.md
    §8.2): an intra-machine a2a followed by staged inter-machine hops,
    bit-identical output (pure routing, no arithmetic), optionally with
    fp8 on the inter-machine wire.
    """
    if layout.u_groups > 1:
        return hier_all_to_all(x, layout, split_axis=split_axis,
                               backend=backend, wire_dtype=wire_dtype)
    return staged_all_to_all(x, layout, split_axis=split_axis,
                             backend=backend)


@jax.named_scope("sp_comm")
def monolithic_all_to_all(
    x: jax.Array, layout: GroupLayout, *, split_axis: int,
    backend: str = "xla", wire_dtype: str | None = None,
) -> jax.Array:
    """Baseline atomic all-to-all (what Ulysses does before Torus).

    Same contract as :func:`grouped_all_to_all`.  Uses ``lax.all_to_all``
    when the ulysses group covers the whole flattened SP axis; otherwise
    falls back to the staged implementation (XLA's all_to_all has no
    subgroup support over a partial logical factor of a named axis).  A
    hierarchical layout (``u_groups > 1``) always takes the two-level
    staged program — that is the point of the decomposition.
    """
    if layout.u_groups > 1:
        return hier_all_to_all(x, layout, split_axis=split_axis,
                               backend=backend, wire_dtype=wire_dtype)
    if (layout.p_ring == 1 and layout.p_ulysses == layout.size
            and backend == "xla"):
        chunks = jnp.stack(jnp.split(x, layout.p_ulysses, axis=split_axis), axis=0)
        # tiled all-to-all over the leading [P_u] axis: slice j -> peer j,
        # received slices re-stacked in source order — one atomic XLA op.
        return lax.all_to_all(
            chunks, layout.axes, split_axis=0, concat_axis=0, tiled=True
        )
    return grouped_all_to_all(x, layout, split_axis=split_axis,
                              backend=backend)


@jax.named_scope("sp_comm")
def ungroup_all_to_all(
    stacked: jax.Array, layout: GroupLayout, *, concat_axis: int,
    backend: str = "xla", wire_dtype: str | None = None,
) -> jax.Array:
    """Inverse transform: send ``stacked[j]`` back to ulysses-peer j and
    concatenate the received chunks along ``concat_axis`` (the fourth
    all-to-all of Ulysses attention, applied to O)."""
    p_u = layout.p_ulysses
    if p_u == 1:
        return jnp.squeeze(stacked, axis=0)
    if layout.u_groups > 1:
        return hier_ungroup(stacked, layout, concat_axis=concat_axis,
                            backend=backend, wire_dtype=wire_dtype)
    if (layout.p_ring == 1 and layout.p_ulysses == layout.size
            and backend == "xla"):
        moved = lax.all_to_all(
            stacked, layout.axes, split_axis=0, concat_axis=0, tiled=True
        )
        return jnp.concatenate(list(moved), axis=concat_axis)
    return staged_ungroup(stacked, layout, concat_axis=concat_axis,
                          backend=backend)
