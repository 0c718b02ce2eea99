"""Torus Attention (paper §4.3, Algorithm 1): chunked, overlappable
all-to-all fused with attention compute.

The monolithic Ulysses all-to-all is decomposed into P_u - 1 point-to-point
stages.  The diagonal chunk (head-slice u of device u's own shard) is
*stationary* — §4.3's key observation — so compute starts immediately, and
each stage-k transfer (a distance-k hop on the torus) is interleaved with
attention on already-resident chunks:

    stage 0        : RingAttn(Q_{t,t}, K_{t,t}, V_{t,t})          (no comm)
    Pull-Q  k=1..N-1: recv Q chunk from u-k; RingAttn(vs local diag KV)
                      while Q chunk for u+k is in flight
    Pull-KV k=1..N-1: recv KV chunk from u-k; RingAttn(all Q vs recv'd KV)
                      while KV chunk for u+k is in flight
    Push-O         : inverse staged all-to-all of O (diagonal stays put)

Q is scheduled before KV exactly as in the paper ("KV doubles the volume
and is harder to hide").  Every per-stage compute is a full RINGATTN over
the intra-machine Ring group, as in Algorithm 1.

Deviations from Algorithm 1 (documented in DESIGN.md §2): the paper defers
the diagonal Q's non-local-KV compute into the Push-O stage so NVSHMEM
pushes overlap it at runtime.  XLA schedules statically, so we fold that
compute into the Pull-KV stages and rely on the latency-hiding scheduler to
overlap the staged Push-O permutes with *subsequent layer* compute — the
same bytes move, on the same hops, in the same stage order.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..comm import Stream, fence, pin, torus_hop
from .collectives import GroupLayout
from .ring import ORACLE, FlashLowering, ring_attention
from .softmax import Partial
from .ulysses import HEAD_AXIS, scatter_o


def _pin(acc: Partial) -> Partial:
    """Serialise the accumulator chain across schedule steps."""
    return Partial(*pin(tuple(acc)))


def _gate(tensors: tuple, acc: Partial):
    """Fence stage inputs on the running accumulator: stage k's attention
    cannot start before stage k-1 merged, so only O(1) score matrices are
    ever live (the channel puts don't pass through the fence and still get
    hoisted/overlapped by the scheduler)."""
    vals, accs = fence(tensors, tuple(acc))
    return vals, Partial(*accs)


def _split_heads(x: jax.Array, p_u: int) -> jax.Array:
    """[B, Ls, H, D] -> [P_u, B, Ls, H/P_u, D]; chunk j is destined to peer j."""
    return jnp.stack(jnp.split(x, p_u, axis=HEAD_AXIS), axis=0)


def _rank_of(layout: GroupLayout, u, r):
    if layout.ulysses_outer:
        return u * layout.p_ring + r
    return r * layout.p_ulysses + u


def _put_slice(acc: Partial, upd: Partial, start: jax.Array,
               axes: Partial) -> Partial:
    """Write ``upd`` into ``acc`` from q row ``start`` (``axes``: each
    field's row axis).  Those rows of ``acc`` are still empty, so this is
    their merge."""
    return Partial(*(lax.dynamic_update_slice_in_dim(a, u, start, axis=ax)
                     for a, u, ax in zip(acc, upd, axes)))


def torus_attention(
    q: jax.Array,  # [B, Ls, Hq, D] natural (seq-sharded) layout
    k: jax.Array,  # [B, Ls, Hkv, D]
    v: jax.Array,
    layout: GroupLayout,
    *,
    scale: float | None = None,
    causal: bool = False,
    window: int | None = None,
    backend: str = "xla",
    wire_dtype: str | None = None,
    flash: bool = False,
) -> jax.Array:
    """Full SwiftFusion attention with the Torus schedule; returns O in the
    original [B, Ls, Hq, D] sharding.

    ``flash`` attends every stage through the flash kernel
    (``ring.FlashLowering``): each chunk is moved into the kernel's layout
    once, as it arrives, the gathered Q is assembled there, and the
    gathered-Q accumulator is the kernel's carried state through the
    Pull-KV stages; O leaves that layout once, at the end.

    ``wire_dtype`` compresses the inter-machine leg of the Push-O when the
    layout is hierarchical (``layout.u_groups > 1``, DESIGN.md §8.2); the
    Pull legs stay exact (Q/KV feed compute directly).

    ``backend="pallas"`` lowers every transfer through the Pallas channel
    backend (semaphore-tracked puts, DESIGN.md §8.1) and runs each
    per-stage RINGATTN through the fused ring_flash kernel."""
    p_u, p_r = layout.p_ulysses, layout.p_ring
    b, ls, _, d = q.shape
    u, r = layout.my_coords()

    qc = _split_heads(q, p_u)  # [P_u, B, Ls, h, D]
    kc = _split_heads(k, p_u)
    vc = _split_heads(v, p_u)
    low = (FlashLowering.of(b, ls, ls, d, q.dtype) if flash else ORACLE)
    ring = partial(ring_attention, layout=layout, scale=scale,
                   causal=causal, window=window, backend=backend,
                   lowering=low)
    q_diag = low.q(jnp.take(qc, u, axis=0))
    k_diag = low.kv(jnp.take(kc, u, axis=0))
    v_diag = low.kv(jnp.take(vc, u, axis=0))
    rows = q_diag.shape[1]  # one Q chunk's rows in the attend's layout

    chunk_pos = lambda src_u: low.q_pos(
        _rank_of(layout, src_u, r) * ls + jnp.arange(ls))
    # position of the diagonal KV chunk as it circulates the Ring group
    diag_kpos_fn = lambda owner_r: _rank_of(layout, u, owner_r) * ls + jnp.arange(ls)

    # gathered-q accumulator, source-u order
    acc = low.empty((q_diag.shape[0], p_u * rows, *q_diag.shape[2:]))
    # ---- stage 0: stationary diagonal chunks, compute starts, no comm
    part = ring(q_diag, k_diag, v_diag, q_pos=chunk_pos(u),
                k_pos_fn=diag_kpos_fn)
    acc = _put_slice(acc, part, u * rows, low.seq_axes)

    stream = Stream("torus", backend=backend)

    # ---- Pull-Q stages: Q chunks arrive one hop-distance k at a time
    q_recv = [None] * p_u  # q_recv[j] = Q chunk from ulysses peer j
    for kstage in range(1, p_u):
        send = jnp.take(qc, (u + kstage) % p_u, axis=0)
        with jax.named_scope("sp_comm"):
            recv = torus_hop(layout, kstage, send, stream=stream,
                             overlaps="diag-KV attend").wait()
        src = (u - kstage) % p_u
        recv = low.q(recv)
        part = ring(recv, k_diag, v_diag, q_pos=chunk_pos(src),
                    k_pos_fn=diag_kpos_fn)
        acc = _pin(_put_slice(acc, part, src * rows, low.seq_axes))
        q_recv[kstage] = (src, recv)

    # assemble the gathered Q (source-u order) for the Pull-KV stages
    q_gather = jnp.zeros((p_u, *q_diag.shape), q_diag.dtype)
    q_gather = lax.dynamic_update_slice_in_dim(q_gather, q_diag[None], u,
                                               axis=0)
    for src, recv in filter(None, q_recv):
        q_gather = lax.dynamic_update_slice_in_dim(q_gather, recv[None], src, axis=0)
    q_gather = jnp.moveaxis(q_gather, 0, 1).reshape(
        q_diag.shape[0], p_u * rows, *q_diag.shape[2:])
    q_pos_all = jnp.concatenate([chunk_pos(j) for j in range(p_u)])

    # ---- Pull-KV stages: KV chunks arrive; all Q attends each new chunk
    for kstage in range(1, p_u):
        src = (u - kstage) % p_u
        with jax.named_scope("sp_comm"):
            k_recv, v_recv = torus_hop(
                layout, kstage,
                jnp.take(kc, (u + kstage) % p_u, axis=0),
                jnp.take(vc, (u + kstage) % p_u, axis=0),
                stream=stream, overlaps="gathered-Q attend").wait()
        (k_recv, v_recv), acc = _gate((k_recv, v_recv), acc)
        kpos_fn = lambda owner_r, s=src: _rank_of(layout, s, owner_r) * ls + jnp.arange(ls)
        acc = ring(q_gather, low.kv(k_recv), low.kv(v_recv),
                   q_pos=q_pos_all, k_pos_fn=kpos_fn, accum=acc)

    # ---- Push-O: staged inverse all-to-all; diagonal O never moves
    o = low.finalize(acc, q.dtype)  # [B, P_u * Ls, h, D]
    return scatter_o(o, layout, backend=backend, wire_dtype=wire_dtype)
