"""Jitted wrappers around the flash_mqkv / ring_flash Pallas kernels.

``flash_attention``     — [B, L, H, D]-layout entry point with GQA,
                          padding to block multiples, position arrays.
``flash_attention_segments`` — the Algorithm-2 use case: one Q against a
                          *list* of discontiguous KV chunks, carrying the
                          online-softmax state across kernel calls and
                          finalizing once (Appendix C).

Dispatch discipline: every variant knob that selects a different lowering
— ``backend`` ("pallas" kernel vs "xla" jnp fallback), ``fused`` (the
ring_flash kernel that issues its own DMA vs plain flash_mqkv), and
``interpret`` — lives in ONE variant tuple (``STATIC_ARGNAMES``), the
``lru_cache`` key of ``_dispatch``, which builds one jitted closure per
key.  A partial key (the historical bug: keying on ``interpret`` but not
``backend``) would hand the xla variant a cached pallas trace and
vice-versa; ``tests/test_ring_flash.py`` counts traces per key to pin
this down.
"""
from __future__ import annotations

import functools
import inspect

import jax
import jax.numpy as jnp

from .flash_mqkv import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_mqkv
from .ref import flash_attention_ref
from .ring_flash import ring_flash_step

# the ONE variant key: lowering variants must never share a jit cache
# entry; asserted below to match _dispatch's signature exactly
STATIC_ARGNAMES = ("causal", "window", "scale", "block_q", "block_k",
                   "masked", "interpret", "backend", "fused")

# Block sizes from the shapes (``block_sizes``; PERF.md, section 6):
# a head's K and V stay resident in VMEM (one kv step, K/V read once a
# head) while, double-buffered, they take at most KV_VMEM_BYTES; a grid
# step's scores are at most SCORE_ELEMS elements.  Per-step work beyond
# the two dots grows with block_q alone (the q scale, the row state, the
# output), so larger steps come closer to the MXU's bound: the compiler's
# static schedule for v5e puts a 4352-token step at 77% of it with 128
# q rows, 86% with 272 and 88% with 544.  On a v5e chip the blocks these
# give at 1280, 2560 and 4352 tokens timed within 3% of the best of the
# candidate blocks measured at each length.
KV_VMEM_BYTES = 16 << 20
SCORE_ELEMS = 1 << 21

# traces per static key (trace-time side effect; the regression counter)
_trace_counts: dict[tuple, int] = {}


def trace_counts() -> dict[tuple, int]:
    """Snapshot of jit traces per static dispatch key."""
    return dict(_trace_counts)


def reset_trace_counts() -> None:
    _trace_counts.clear()


def _pad_to(x: jax.Array, axis: int, mult: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _largest_divisor(n: int, mult: int, cap: int) -> int | None:
    """The largest multiple of ``mult`` that divides ``n`` and is at most
    ``cap``, or None."""
    for b in range(cap // mult * mult, 0, -mult):
        if n % b == 0:
            return b
    return None


def block_sizes(lq: int, lk: int, d: int, itemsize: int) -> tuple[int, int]:
    """(block_q, block_k) for ``flash_mqkv`` from the shapes alone.

    K and V of a head are one kv block when they fit KV_VMEM_BYTES: the
    block spans the array, so it tiles at any length, with no padding and
    no mask (at 4,444 keys and head_dim 64 that timed 10% faster on a v5e
    than 4,480 padded and masked).  Else the largest 128-multiple that
    fits and divides the length padded to whole 128-lane tiles.  block_q
    is the largest multiple of the sublane tile (8 rows of f32, 16 of
    bf16) that divides Lq and keeps the scores within SCORE_ELEMS; where
    no divisor comes within half of that cap, q is padded to the fewest
    blocks within the cap, each as small as covers Lq."""
    sub = 8 * max(4 // itemsize, 1)
    kv_cap = KV_VMEM_BYTES // (4 * d * itemsize)
    bk = (lk if lk <= kv_cap
          else _largest_divisor(_round_up(lk, 128), 128, max(kv_cap, 128)))
    cap = max(SCORE_ELEMS // bk // sub * sub, sub)
    if lq <= cap:
        return _round_up(lq, sub), bk
    bq = _largest_divisor(lq, sub, cap)
    if bq is not None and 2 * bq >= cap:
        return bq, bk
    return _round_up(-(-lq // -(-lq // cap)), sub), bk


def _flatten_heads(x: jax.Array) -> jax.Array:
    b, l, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, l, d)


def _unflatten_heads(x: jax.Array, b: int, h: int) -> jax.Array:
    bh, l, d = x.shape
    return x.reshape(b, h, l, d).transpose(0, 2, 1, 3)


def _step(qf, kf, vf, qpp, kpp, *, group, scale, causal, window, state,
          finalize, block_q, block_k, interpret, backend, fused,
          masked=True):
    """One kernel step on flattened [BH, L, D] operands, by variant."""
    if backend == "xla":
        kr = jnp.repeat(kf, group, axis=0) if group > 1 else kf
        vr = jnp.repeat(vf, group, axis=0) if group > 1 else vf
        out = flash_attention_ref(
            qf, kr, vr, qpp, kpp, scale=scale, causal=causal, window=window,
            state=state, finalize=finalize)
        return out if not finalize else (out, None, None)
    if fused:
        (o, l, m), _ = ring_flash_step(
            qf, kf, vf, qpp, kpp, group=group, scale=scale, causal=causal,
            window=window, state=state, finalize=finalize,
            block_q=block_q, block_k=block_k, interpret=interpret)
        return o, l, m
    return flash_mqkv(
        qf, kf, vf, qpp, kpp, group=group, scale=scale, causal=causal,
        window=window, state=state, finalize=finalize,
        block_q=block_q, block_k=block_k, masked=masked, interpret=interpret)


@functools.lru_cache(maxsize=None)
def _dispatch(causal, window, scale, block_q, block_k, masked, interpret,
              backend, fused):
    """Build (and cache) the jitted impl for one static-variant key.

    The lru_cache key IS the full variant tuple (one jitted closure per
    key — the knobs are closure constants, not jit static args), so no
    two variants can collide on a cache entry.
    """
    key = (causal, window, scale, block_q, block_k, masked, interpret,
           backend, fused)

    @jax.jit
    def impl(q, k, v, q_pos, k_pos):
        _trace_counts[key] = _trace_counts.get(key, 0) + 1
        b, lq, hq, d = q.shape
        _, lk, hkv, _ = k.shape
        group = hq // hkv
        bq, bk = block_sizes(lq, lk, d, q.dtype.itemsize)
        if block_q is not None:
            bq = min(block_q, max(8, lq))
        if block_k is not None:
            bk = min(block_k, max(8, lk))
        qpp = _pad_to(q_pos.astype(jnp.int32), 0, bq, value=0)
        kpp = _pad_to(k_pos.astype(jnp.int32), 0, bk, value=-1)
        mask = masked or kpp.shape[0] != lk  # padded K is masked out
        if backend == "pallas" and not fused and d % 128 == 0:
            # heads stay packed in the lanes: no transpose in or out
            pack = lambda x, blk: _pad_to(x.reshape(b, x.shape[1], -1), 1,
                                          blk)
            o, _, _ = flash_mqkv(
                pack(q, bq), pack(k, bk), pack(v, bk), qpp, kpp, group=group,
                scale=scale, causal=causal, window=window, block_q=bq,
                block_k=bk, masked=mask, heads=hq,
                interpret=interpret)
            return o[:, :lq].reshape(b, lq, hq, d)
        qf = _pad_to(_flatten_heads(q), 1, bq)
        kf = _pad_to(_flatten_heads(k), 1, bk)
        vf = _pad_to(_flatten_heads(v), 1, bk)
        o, _, _ = _step(
            qf, kf, vf, qpp, kpp, group=group, scale=scale, causal=causal,
            window=window, state=None, finalize=True, block_q=bq, block_k=bk,
            interpret=interpret, backend=backend, fused=fused, masked=mask)
        return _unflatten_heads(o[:, :lq], b, hq)

    return impl


# the canonical key ordering and the dispatch signature must not drift
assert tuple(
    inspect.signature(_dispatch.__wrapped__).parameters) == STATIC_ARGNAMES


def flash_attention(
    q: jax.Array,  # [B, Lq, Hq, D]
    k: jax.Array,  # [B, Lk, Hkv, D]
    v: jax.Array,
    q_pos: jax.Array | None = None,  # [Lq]
    k_pos: jax.Array | None = None,  # [Lk]
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    backend: str = "pallas",
    fused: bool = False,
) -> jax.Array:
    """Drop-in flash attention; returns [B, Lq, Hq, D].

    Blocks not given come from the shapes (``block_sizes``).  Without
    ``k_pos``, a causal or window mask, or K padding, the kernel skips
    its mask work altogether.

    ``backend="pallas"`` runs the Pallas kernel (``fused=True`` selects
    the ring_flash variant that also issues its forwarding DMA);
    ``backend="xla"`` runs the pure-jnp lowering (platforms without
    Pallas).  All three produce the same values.  Note ``fused=True``
    here discards the forward buffers (and pays their copy) — its
    consumer is core/ring.py's pallas path; on this entry point it
    exists for parity and dispatch testing, not as a perf knob.
    """
    lq, lk = q.shape[1], k.shape[1]
    masked = causal or window is not None or k_pos is not None
    if q_pos is None:
        q_pos = jnp.arange(lq, dtype=jnp.int32)
    if k_pos is None:
        k_pos = jnp.arange(lk, dtype=jnp.int32)
    impl = _dispatch(causal, window, scale, block_q, block_k, masked,
                     interpret, backend, fused)
    return impl(q, k, v, q_pos, k_pos)


def flash_attention_segments(
    q: jax.Array,  # [B, Lq, Hq, D]
    segments: list[tuple[jax.Array, jax.Array, jax.Array]],  # (k, v, k_pos)
    q_pos: jax.Array | None = None,
    *,
    causal: bool = False,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
    backend: str = "pallas",
    fused: bool = False,
) -> jax.Array:
    """Attention of one Q against multiple discontiguous KV chunks — the
    RINGATTN inner loop of Algorithm 1 with the Algorithm-2 fused merge:
    the (O', l, m) state is carried across kernel calls, one division at
    the very end."""
    b, lq, hq, d = q.shape
    if q_pos is None:
        q_pos = jnp.arange(lq, dtype=jnp.int32)
    bq = min(block_q, max(8, lq))
    qf = _pad_to(_flatten_heads(q), 1, bq)
    qpp = _pad_to(q_pos.astype(jnp.int32), 0, bq, value=0)

    state = None
    for i, (k, v, k_pos) in enumerate(segments):
        _, lk, hkv, _ = k.shape
        group = hq // hkv
        bk = min(block_k, max(8, lk))
        kf = _pad_to(_flatten_heads(k), 1, bk)
        vf = _pad_to(_flatten_heads(v), 1, bk)
        kpp = _pad_to(k_pos.astype(jnp.int32), 0, bk, value=-1)
        last = i == len(segments) - 1
        out = _step(
            qf, kf, vf, qpp, kpp, group=group, scale=scale, causal=causal,
            window=window, state=state, finalize=last, block_q=bq, block_k=bk,
            interpret=interpret, backend=backend, fused=fused)
        if last:
            o = out[0]
        else:
            state = out
    return _unflatten_heads(o[:, :lq].astype(q.dtype), b, hq)
