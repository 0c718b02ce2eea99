"""Online-softmax partial attention and merge algebra (paper Appendix C).

SwiftFusion's Ring and Torus attention both compute attention of one query
chunk against *multiple* KV chunks that arrive at different times.  Each
partial computation produces a triplet ``A_i = (O'_i, l_i, m_i)`` where

    m_i = rowmax(Q K_i^T * scale)
    l_i = rowsum(exp(Q K_i^T * scale - m_i))
    O'_i = exp(Q K_i^T * scale - m_i) @ V_i        (FlashAttention-2 style:
                                                    *unnormalised* by l_i)

and two triplets merge associatively (Appendix C, eq. 2-3):

    m = max(m_i, m_j)
    l = l_i e^{m_i - m} + l_j e^{m_j - m}
    O' = O'_i e^{m_i - m} + O'_j e^{m_j - m}

with one division ``O = O'/l`` at the very end (``finalize``).

All functions are pure jnp and GQA-aware; they are the oracle against which
the Pallas kernel (kernels/flash_mqkv.py) and every distributed schedule is
validated.

Shapes (B = batch, Lq/Lk = seq, Hq/Hkv = heads, D = head dim):
    q: [B, Lq, Hq, D]    k, v: [B, Lk, Hkv, D]
    o: [B, Lq, Hq, D]    l, m: [B, Hq, Lq]
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = float("-inf")


class Partial(NamedTuple):
    """FA2-style intermediate result A' = (O' = O*l, l, m)."""

    o: jax.Array  # [B, Lq, Hq, D], unnormalised
    l: jax.Array  # [B, Hq, Lq]
    m: jax.Array  # [B, Hq, Lq]


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Attention masking for one (q-chunk, kv-chunk) pair.

    Positions are *global* sequence positions, so chunked/distributed
    schedules apply exactly the same mask the single-device computation
    would, even when a gathered chunk is discontinuous in the global
    sequence (paper §4.3: received chunks "can be discontinuous").

    Either give scalar offsets (``q_offset``/``k_offset``, chunk is then
    contiguous from there) or explicit per-element position arrays
    (``q_pos``/``k_pos``), which take precedence.

    ``causal``: standard autoregressive mask (q attends to k ≤ q).
    ``window``: sliding-window size; q attends to k in
                (q_pos - window, q_pos].  ``None`` = unlimited.
    ``valid_k``: optional [Lk] bool — False masks a key out entirely
                 (used by the decode path for unwritten cache slots).
    """

    causal: bool = False
    window: int | None = None
    q_offset: int | jax.Array = 0
    k_offset: int | jax.Array = 0
    q_pos: jax.Array | None = None
    k_pos: jax.Array | None = None
    valid_k: jax.Array | None = None

    def bias(self, lq: int, lk: int, dtype=jnp.float32) -> jax.Array | None:
        if not self.causal and self.window is None and self.valid_k is None:
            return None
        q_pos = self.q_pos if self.q_pos is not None else jnp.arange(lq) + self.q_offset
        k_pos = self.k_pos if self.k_pos is not None else jnp.arange(lk) + self.k_offset
        ok = jnp.ones((lq, lk), dtype=bool)
        if self.causal:
            ok &= q_pos[:, None] >= k_pos[None, :]
        if self.window is not None:
            ok &= k_pos[None, :] > (q_pos[:, None] - self.window)
        if self.valid_k is not None:
            ok &= self.valid_k[None, :]
        return jnp.where(ok, 0.0, NEG_INF).astype(dtype)


def empty_partial(batch: int, lq: int, hq: int, d: int, dtype=jnp.float32) -> Partial:
    """Identity element of the merge monoid."""
    return Partial(
        o=jnp.zeros((batch, lq, hq, d), dtype),
        l=jnp.zeros((batch, hq, lq), dtype),
        m=jnp.full((batch, hq, lq), NEG_INF, dtype),
    )


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """[B, L, Hkv, D] -> [B, L, Hkv * n_rep, D] (GQA broadcast)."""
    if n_rep == 1:
        return x
    b, l, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, l, h, n_rep, d)).reshape(
        b, l, h * n_rep, d
    )


def attend_partial(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    mask: MaskSpec | None = None,
    precision=jax.lax.Precision.HIGHEST,
) -> Partial:
    """Unnormalised attention of q against one KV chunk (Appendix C eq. 1)."""
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    assert hq % hkv == 0, f"GQA requires Hkv | Hq, got {hq=} {hkv=}"
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum("blhd,bkhd->bhlk", q, k, precision=precision) * scale
    s = s.astype(jnp.float32)
    if mask is not None:
        bias = mask.bias(lq, lk)
        if bias is not None:
            s = s + bias[None, None]
    m = jnp.max(s, axis=-1)  # [B, Hq, Lq]
    # Fully-masked rows have m == -inf; exp(-inf - -inf) would be NaN.
    safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - safe_m[..., None])  # [B, Hq, Lq, Lk]
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    l = jnp.sum(p, axis=-1)  # [B, Hq, Lq]
    o = jnp.einsum("bhlk,bkhd->blhd", p.astype(v.dtype), v, precision=precision)
    return Partial(o=o.astype(jnp.float32), l=l, m=m)


def merge(a: Partial, b: Partial) -> Partial:
    """Associative, commutative merge of two partials (Appendix C eq. 2-3)."""
    m = jnp.maximum(a.m, b.m)
    safe = lambda mi: jnp.where(jnp.isneginf(mi) & jnp.isneginf(m), 0.0, mi - m)
    ea = jnp.exp(safe(a.m))
    eb = jnp.exp(safe(b.m))
    l = a.l * ea + b.l * eb
    # broadcast [B,Hq,Lq] -> [B,Lq,Hq,1] for the output tensor layout
    t = lambda e: jnp.swapaxes(e, 1, 2)[..., None]
    o = a.o * t(ea) + b.o * t(eb)
    return Partial(o=o, l=l, m=m)


def finalize(p: Partial, dtype=None) -> jax.Array:
    """O = O' / l with one division at the end (Appendix C 'optimizing FP ops')."""
    l = jnp.swapaxes(p.l, 1, 2)[..., None]  # [B, Lq, Hq, 1]
    o = p.o / jnp.where(l == 0.0, 1.0, l)
    return o.astype(dtype or p.o.dtype)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    mask: MaskSpec | None = None,
) -> jax.Array:
    """Plain single-device softmax attention — the ground-truth oracle."""
    return finalize(attend_partial(q, k, v, scale=scale, mask=mask),
                    dtype=q.dtype)


def attend_chunked(
    q: jax.Array,
    kv_chunks: list[tuple[jax.Array, jax.Array, int]],
    *,
    scale: float | None = None,
    causal: bool = False,
    window: int | None = None,
    q_offset: int = 0,
) -> Partial:
    """Attention of q against a list of (k, v, k_offset) chunks, merged.

    Mirrors what Ring/Torus attention computes step-by-step; used by tests
    to check chunk-order invariance.
    """
    b, lq, hq, d = q.shape
    acc = empty_partial(b, lq, hq, d)
    for k, v, k_off in kv_chunks:
        mask = MaskSpec(causal=causal, window=window, q_offset=q_offset, k_offset=k_off)
        acc = merge(acc, attend_partial(q, k, v, scale=scale, mask=mask))
    return acc
