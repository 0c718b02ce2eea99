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

Each attend has one of two lowerings, the same for every stage of a
schedule: ``ORACLE``, ``attend_partial`` on the operands as given, merged
into the running partial; or a ``FlashLowering``, the ``flash_mqkv``
kernel on operands moved once into its layout, with the running
(O', l, m) carried through the kernel as its state (Algorithm 2's fused
merge).  ``core.strategy.attention_lowering`` chooses.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..comm import Stream, fence, ring_shift
from ..comm import profiler as _profiler
from ..comm import trace as _trace
from ..kernels.flash_mqkv import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_mqkv
from ..kernels.ops import _flatten_heads, _pad_to, _round_up, block_sizes
from ..kernels.ring_flash import ring_flash_step
from .collectives import GroupLayout
from .softmax import (MaskSpec, Partial, attend_partial, empty_partial,
                      finalize, merge)

# maps the ring coordinate (traced int32) owning the chunk -> [Lk] positions
KPosFn = Callable[[jax.Array], jax.Array]


class OracleLowering:
    """Attends by ``attend_partial``: operands as given ([B, L, H, D]),
    the running state a ``Partial`` as ``core.softmax`` lays it out."""

    seq_axes = Partial(o=1, l=2, m=2)  # each state field's row axis

    @staticmethod
    def q(x):
        return x

    kv = q_pos = k_pos = q

    @staticmethod
    def empty(q_shape) -> Partial:
        return empty_partial(*q_shape)

    @staticmethod
    def finalize(acc: Partial, dtype) -> jax.Array:
        return finalize(acc, dtype=dtype)


ORACLE = OracleLowering()


class FlashLowering(NamedTuple):
    """Attends by the ``flash_mqkv`` kernel, for q chunks of ``q_len``
    rows and KV chunks of ``k_len``: heads folded into the batch
    ([B·H, L, D]), the state (O', l, m) as [B·H, L, D] and [B·H, L, 1]
    (``flash_mqkv``'s ``keepdims``), q rows padded to whole ``block_q``
    blocks (computed and dropped), K and V to whole ``block_k`` blocks
    (positions -1, masked).  The blocks come from the chunks' shape
    (``kernels.ops.block_sizes``)."""

    batch: int
    q_len: int
    k_len: int
    block_q: int
    block_k: int

    seq_axes = Partial(o=1, l=1, m=1)

    @classmethod
    def of(cls, batch, q_len, k_len, head_dim, dtype) -> "FlashLowering":
        bq, bk = block_sizes(q_len, k_len, head_dim,
                             jnp.dtype(dtype).itemsize)
        return cls(batch, q_len, k_len, bq, bk)

    def q(self, x):  # [B, Lq, H, D] -> [B·H, Lq padded, D]
        return _pad_to(_flatten_heads(x), 1, self.block_q)

    def kv(self, x):
        return _pad_to(_flatten_heads(x), 1, self.block_k)

    def q_pos(self, p):
        return _pad_to(p.astype(jnp.int32), 0, self.block_q)

    def k_pos(self, p):
        return _pad_to(p.astype(jnp.int32), 0, self.block_k, value=-1)

    def empty(self, q_shape) -> Partial:
        bh, lq, d = q_shape
        return Partial(o=jnp.zeros((bh, lq, d), jnp.float32),
                       l=jnp.zeros((bh, lq, 1), jnp.float32),
                       m=jnp.full((bh, lq, 1), -jnp.inf, jnp.float32))

    def finalize(self, acc: Partial, dtype) -> jax.Array:
        """O = O' / l over whole chunks of q rows, back to [B, L, H, D]
        with each chunk's padding dropped: the one transpose out."""
        bh, lq, d = acc.o.shape
        h, rows = bh // self.batch, _round_up(self.q_len, self.block_q)
        o = (acc.o / jnp.where(acc.l == 0.0, 1.0, acc.l)).astype(dtype)
        o = o.reshape(self.batch, h, lq // rows, rows, d)[:, :, :, :self.q_len]
        return o.transpose(0, 2, 3, 1, 4).reshape(self.batch, -1, h, d)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _flash_attend(q, k, v, q_pos, k_pos, state, scale, causal, window,
                  block_q, block_k, masked) -> Partial:
    """One attend through the flash kernel in ``FlashLowering``'s layout,
    the running (O', l, m) carried in (``state``; None when fresh) and
    out.  The gradient is the oracle's (``_oracle_attend``, as
    ``strategy._one_chip_flash`` at SP=1): a Pallas call has no transpose
    rule."""
    return Partial(*flash_mqkv(
        q, k, v, q_pos, k_pos, group=q.shape[0] // k.shape[0], scale=scale,
        causal=causal, window=window, state=state, finalize=False,
        block_q=block_q, block_k=block_k, masked=masked, keepdims=True))


def _oracle_attend(q, k, v, q_pos, k_pos, state, scale, causal, window
                   ) -> Partial:
    """``_flash_attend``'s values by ``attend_partial``, merged into the
    carried state: the kernel's [B·H, L, D] layout viewed as a batch of
    B·Hkv with the ``group`` q heads of each KV head (GQA), pad keys
    (position -1) masked out."""
    bhkv, lq = k.shape[0], q.shape[1]
    group = q.shape[0] // bhkv
    heads = lambda x: x.reshape(bhkv, group, lq, -1).transpose(0, 2, 1, 3)
    rows = lambda x: x[..., 0].reshape(bhkv, group, lq)
    mask = MaskSpec(causal=causal, window=window, q_pos=q_pos, k_pos=k_pos,
                    valid_k=k_pos >= 0)
    part = attend_partial(heads(q), k[:, :, None], v[:, :, None],
                          scale=scale, mask=mask)
    if state is not None:
        part = merge(Partial(heads(state.o), rows(state.l), rows(state.m)),
                     part)
    flat = lambda x: x.reshape(bhkv * group, lq, 1)
    return Partial(part.o.transpose(0, 2, 1, 3).reshape(q.shape[0], lq, -1),
                   flat(part.l), flat(part.m))


def _flash_attend_fwd(q, k, v, q_pos, k_pos, state, *static):
    return (_flash_attend(q, k, v, q_pos, k_pos, state, *static),
            (q, k, v, q_pos, k_pos, state))


def _flash_attend_bwd(scale, causal, window, block_q, block_k, masked,
                      res, g):
    q, k, v, q_pos, k_pos, state = res
    _, vjp = jax.vjp(lambda q, k, v, st: _oracle_attend(
        q, k, v, q_pos, k_pos, st, scale, causal, window), q, k, v, state)
    dq, dk, dv, dstate = vjp(g)
    return dq, dk, dv, None, None, dstate


_flash_attend.defvjp(_flash_attend_fwd, _flash_attend_bwd)


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
    backend: str = "xla",
    lowering: OracleLowering | FlashLowering = ORACLE,
) -> Partial:
    """Run P_r ring steps, unrolled; returns the merged partial (not
    finalized).  Unrolled steps let XLA schedule each permute against the
    next step's compute, and let HLO cost analysis count every step.

    ``lowering`` says how each step attends (the module docstring): q, k,
    v, ``q_pos`` and ``accum`` come in its layout, and the result goes out
    in it; ``k_pos_fn`` gives a chunk's positions unpadded.

    ``backend="pallas"`` runs the fused path (DESIGN.md §8.1): each ring
    step is ONE ``kernels.ring_flash`` call that carries the (O', l, m)
    online-softmax state in VMEM *and* issues the next-step KV put from
    inside the kernel, the paper's Algorithm-2 overlap."""
    if backend == "pallas":
        return _ring_attention_pallas(
            q, k, v, layout, q_pos=q_pos, k_pos_fn=k_pos_fn, scale=scale,
            causal=causal, window=window, accum=accum)
    p_r = layout.p_ring
    acc = accum
    masked = causal or window is not None

    if isinstance(lowering, FlashLowering):
        masked = masked or lowering.k_len % lowering.block_k != 0
        qp = (q_pos if q_pos is not None
              else jnp.zeros(q.shape[1], jnp.int32))

        def attend(acc, kc, vc, owner_r):
            kp = (k_pos_fn(owner_r) if k_pos_fn is not None
                  else jnp.arange(lowering.k_len))
            return _flash_attend(
                q, kc, vc, qp, lowering.k_pos(kp), acc, scale, causal,
                window, lowering.block_q, lowering.block_k, masked)
    else:
        def attend(acc, kc, vc, owner_r):
            mask = None if not masked else MaskSpec(
                causal=causal, window=window, q_pos=q_pos,
                k_pos=k_pos_fn(owner_r) if k_pos_fn is not None else None)
            part = attend_partial(q, kc, vc, scale=scale, mask=mask)
            return part if acc is None else merge(acc, part)

    _, my_r = layout.my_coords()
    if p_r == 1:
        # pure-Ulysses plan: no ring rotation, but this local attend is
        # still the compute the torus hops are scheduled to hide — mark it
        # so per-stage traces stay complete for overlap accounting
        out = attend(acc, k, v, my_r)
        _profiler.mark_compute("local attend", layout.axes, (k, v),
                               tuple(out), stream="ring")
        return out

    stream = Stream("ring")
    kc, vc = k, v
    for s in range(p_r - 1):
        # issue the next step's transfer first (double buffer), then fence
        # this step's attend inputs on the accumulator, so that only one
        # step's score matrix is live; the put does not pass through the
        # fence and still overlaps the attend
        with jax.named_scope("sp_comm"):
            nxt = ring_shift(layout, kc, vc, stream=stream,
                             overlaps="ring attend")
        if acc is not None:  # a fresh first step has nothing to wait on
            (kc, vc), accs = fence((kc, vc), tuple(acc))
            acc = Partial(*accs)
        owner = (my_r - s) % p_r  # ring rank whose shard I currently hold
        acc = attend(acc, kc, vc, owner)
        _profiler.mark_compute("ring attend", layout.axes, (kc, vc),
                               tuple(acc), stream=stream.name)
        kc, vc = nxt.payload
    # last step: compute only, no further transfer (2(P-1)/P volume, §2.2)
    owner = (my_r - (p_r - 1)) % p_r
    out = attend(acc, kc, vc, owner)
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
) -> Partial:
    """P_r fused ring steps: kernel-carried (O', l, m) + in-kernel puts.

    The KV chunk circulates in *flattened padded* layout ([B·Hkv, Lk_pad,
    D], padding masked via k_pos = -1), so the kernel's forward buffers
    can be handed to the channel unmodified at every step.
    """
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

    stream = Stream("ring", backend="pallas")
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
                block_q=bq, block_k=bk)
            fut = ch.put_fused(kfwd, vfwd, overlaps="ring attend")
            _trace.mark_compute("ring attend", stream=stream.name)
        else:
            # last step: compute only (2(P-1)/P volume, §2.2)
            o, l, m = flash_mqkv(
                qf, kc, vc, qpp, kpos_for(owner), group=group, scale=scale,
                causal=causal, window=window, state=state, finalize=False,
                block_q=bq, block_k=bk)
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
