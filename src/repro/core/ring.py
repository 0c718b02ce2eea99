"""Ring Attention over a logical ring group (paper §2.2, Algorithm 1 RINGATTN).

Per-device view: the KV shard (possibly a Ulysses-gathered concatenation of
several chunks) rotates around the Ring group in P_r steps while each device
keeps its local Q and accumulates the online-softmax partial ``(O', l, m)``.

The KV transfer for step s+1 is issued *before* the attention compute of
step s (double buffering) through a one-sided ``repro.comm`` channel
(DESIGN.md §8): the ``put`` starts the collective-permute DMA, the
``fence`` is the receiver-side signal wait — the TPU equivalent of the
paper's stream-ordered one-sided pulls (Algorithm 1 RINGATTN lines 2-7:
pull next, compute current, wait).

Masking is exact under arbitrary chunk layouts: the caller supplies a
*position function* mapping the ring rank that owns the currently-held KV
to the global positions of its elements, so causal/sliding-window masks are
identical to the single-device computation no matter where a chunk
currently sits.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..comm import Stream, fence, ring_shift
from ..comm import profiler as _profiler
from ..comm import trace as _trace
from .collectives import GroupLayout
from .softmax import (MaskSpec, Partial, attend_partial,
                      attend_partial_blockwise, empty_partial, merge)

# maps the ring coordinate (traced int32) owning the chunk -> [Lk] positions
KPosFn = Callable[[jax.Array], jax.Array]


def ring_attention(
    q: jax.Array,  # [B, Lq, Hq, D] local query (stays put)
    k: jax.Array,  # [B, Lk, Hkv, D] local KV shard (rotates)
    v: jax.Array,
    layout: GroupLayout,
    *,
    q_pos: jax.Array | None,  # [Lq] global positions of q (None = no masking)
    k_pos_fn: KPosFn | None,
    scale: float | None = None,
    causal: bool = False,
    window: int | None = None,
    accum: Partial | None = None,
    unroll: bool = False,
    kv_block: int | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
) -> Partial:
    """Run P_r ring steps; returns the merged partial (not finalized).

    ``kv_block`` caps the materialized score matrix per attend (see
    softmax.attend_partial_blockwise).

    ``backend="pallas"`` runs the fused path (DESIGN.md §8.1): each ring
    step is ONE ``kernels.ring_flash`` call that carries the (O', l, m)
    online-softmax state in VMEM *and* issues the next-step KV put from
    inside the kernel, the paper's Algorithm-2 overlap.  The pallas path
    is always step-unrolled (one kernel per step) and ignores
    ``kv_block`` (the kernel has its own VMEM blocking); ``interpret``
    selects the interpreter-mode lowering (None follows the platform)."""
    if backend == "pallas":
        return _ring_attention_pallas(
            q, k, v, layout, q_pos=q_pos, k_pos_fn=k_pos_fn, scale=scale,
            causal=causal, window=window, accum=accum, interpret=interpret)
    def _attend(q_, k_, v_, mask):
        if kv_block is not None:
            return attend_partial_blockwise(q_, k_, v_, scale=scale,
                                            mask=mask, kv_block=kv_block)
        return attend_partial(q_, k_, v_, scale=scale, mask=mask)
    p_r = layout.p_ring
    b, lq, hq, d = q.shape
    acc = accum if accum is not None else empty_partial(b, lq, hq, d)
    masked = causal or window is not None

    def mask_for(owner_r):
        if not masked:
            return None
        return MaskSpec(
            causal=causal,
            window=window,
            q_pos=q_pos,
            k_pos=k_pos_fn(owner_r) if k_pos_fn is not None else None,
        )

    _, my_r = layout.my_coords()
    if p_r == 1:
        # pure-Ulysses plan: no ring rotation, but this local attend is
        # still the compute the torus hops are scheduled to hide — mark it
        # so per-stage traces stay complete for overlap accounting
        out = merge(acc, _attend(q, k, v, mask_for(my_r)))
        _profiler.mark_compute("local attend", layout.axes, (k, v),
                               tuple(out), stream="ring")
        return out

    stream = Stream("ring")

    def body(s, carry):
        kc, vc, acc = carry
        # issue next-step transfer first (double buffer), compute current
        with jax.named_scope("sp_comm"):
            nxt = ring_shift(layout, kc, vc, stream=stream,
                             overlaps="ring attend")
        owner = (my_r - s) % p_r  # ring rank whose shard I currently hold
        acc = merge(acc, _attend(q, kc, vc, mask_for(owner)))
        _profiler.mark_compute("ring attend", layout.axes, (kc, vc),
                               tuple(acc), stream=stream.name)
        return (*nxt.payload, acc)

    if unroll:
        # unrolling lets XLA schedule permutes across step boundaries at the
        # cost of HLO size; fori_loop keeps HLO O(1) in P_r.  The fence on
        # acc stops the scheduler from materializing every step's score
        # matrix at once (puts don't pass through the fence, so they still
        # overlap with compute).
        kc, vc = k, v
        for s in range(p_r - 1):
            # fence this step's attend inputs on the accumulator so only one
            # step's score matrix is live; the next put stays independent
            with jax.named_scope("sp_comm"):
                nxt = ring_shift(layout, kc, vc, stream=stream,
                                 overlaps="ring attend")
            (kc_g, vc_g), accs = fence((kc, vc), tuple(acc))
            acc = Partial(*accs)
            owner = (my_r - s) % p_r
            acc = merge(acc, _attend(q, kc_g, vc_g, mask_for(owner)))
            _profiler.mark_compute("ring attend", layout.axes,
                                   (kc_g, vc_g), tuple(acc),
                                   stream=stream.name)
            kc, vc = nxt.payload
    else:
        kc, vc, acc = lax.fori_loop(0, p_r - 1, body, (k, v, acc))
    # last step: compute only, no further transfer (2(P-1)/P volume, §2.2)
    owner = (my_r - (p_r - 1)) % p_r
    out = merge(acc, _attend(q, kc, vc, mask_for(owner)))
    _profiler.mark_compute("ring attend", layout.axes, (kc, vc),
                           tuple(out), stream=stream.name)
    return out


# ---------------------------------------------------------------------------
# fused Pallas path (DESIGN.md §8.1)
# ---------------------------------------------------------------------------

def _ring_attention_pallas(
    q: jax.Array,  # [B, Lq, Hq, D]
    k: jax.Array,  # [B, Lk, Hkv, D]
    v: jax.Array,
    layout: GroupLayout,
    *,
    q_pos: jax.Array | None,
    k_pos_fn: KPosFn | None,
    scale: float | None,
    causal: bool,
    window: int | None,
    accum: Partial | None,
    interpret: bool,
) -> Partial:
    """P_r fused ring steps: kernel-carried (O', l, m) + in-kernel puts.

    The KV chunk circulates in *flattened padded* layout ([B·Hkv, Lk_pad,
    D], padding masked via k_pos = -1), so the kernel's forward buffers
    can be handed to the channel unmodified at every step.
    """
    from ..kernels.flash_mqkv import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                      flash_mqkv)
    from ..kernels.ops import _flatten_heads, _pad_to
    from ..kernels.ring_flash import ring_flash_step

    def _flatten_pad(x, block):  # [B, L, H, D] -> [B*H, L_pad, D]
        return _pad_to(_flatten_heads(x), 1, block)

    def _pad_pos(p, block, value):
        return _pad_to(p.astype(jnp.int32), 0, block, value=value)

    p_r = layout.p_ring
    b, lq, hq, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = min(DEFAULT_BLOCK_Q, max(8, lq))
    bk = min(DEFAULT_BLOCK_K, max(8, lk))
    _, my_r = layout.my_coords()

    qf = _flatten_pad(q, bq)
    qpp = _pad_pos(q_pos if q_pos is not None
                   else jnp.arange(lq, dtype=jnp.int32), bq, 0)
    kc, vc = _flatten_pad(k, bk), _flatten_pad(v, bk)

    def kpos_for(owner):
        base = (k_pos_fn(owner) if k_pos_fn is not None
                else jnp.arange(lk, dtype=jnp.int32))
        return _pad_pos(base, bk, -1)

    stream = Stream("ring", backend="pallas", interpret=interpret)
    state = None
    fut = None
    for s in range(p_r):
        if fut is not None:
            kc, vc = fut.wait()
        owner = (my_r - s) % p_r
        if s < p_r - 1:
            # fused step: the kernel issues the next-step put at its first
            # grid step and drains it after its last compute block
            ch = stream.channel(layout.axes, layout.ring_perm(1),
                                f"shift1.s{s}")
            stream.next_stage()
            (o, l, m), (kfwd, vfwd) = ring_flash_step(
                qf, kc, vc, qpp, kpos_for(owner), group=group, scale=scale,
                causal=causal, window=window, state=state, finalize=False,
                block_q=bq, block_k=bk, interpret=interpret)
            fut = ch.put_fused(kfwd, vfwd, overlaps="ring attend")
            _trace.mark_compute("ring attend", stream=stream.name)
        else:
            # last step: compute only (2(P-1)/P volume, §2.2)
            o, l, m = flash_mqkv(
                qf, kc, vc, qpp, kpos_for(owner), group=group, scale=scale,
                causal=causal, window=window, state=state, finalize=False,
                block_q=bq, block_k=bk, interpret=interpret)
        _profiler.mark_compute("ring attend", layout.axes, (kc, vc),
                               (o, l, m), stream=stream.name)
        state = (o, l, m)

    o, l, m = state
    part = Partial(
        o=o.reshape(b, hq, -1, d)[:, :, :lq].transpose(0, 2, 1, 3),
        l=l.reshape(b, hq, -1)[:, :, :lq],
        m=m.reshape(b, hq, -1)[:, :, :lq],
    )
    return part if accum is None else merge(accum, part)
