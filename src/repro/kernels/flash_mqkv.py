"""Pallas TPU kernel: FlashAttention over multiple discontiguous Q/KV chunks
with a fused online-softmax merge — the TPU adaptation of the paper's
Algorithm 2 (Appendix B).

What the CUDA kernel does with warp-level mma + per-tensor binary search,
the TPU version does with MXU-aligned VMEM tiles and *position arrays*:
instead of launching one kernel per received chunk (kernel-launch overhead,
the problem Algorithm 2 solves), the caller concatenates any number of
discontiguous chunks and passes their **global positions**; padding slots
carry ``k_pos = -1`` and are masked in-kernel.  Exact causal/sliding-window
masks are computed from positions, so a chunk can sit anywhere in memory.

The Appendix-C merge is fused the same way as Algorithm 2 lines 11-15: the
kernel accepts carried-in ``(O', l, m)`` running state from previous calls
(earlier Ring/Torus steps), updates it across its KV blocks in VMEM
scratch, and divides by ``l`` only when ``finalize`` is set (FA2, eq. 3).

Grid: (batch·heads, Lq/block_q, Lk/block_k); the KV dimension is the
innermost "arbitrary" (sequential) axis, so the running (m, l, acc) state
lives in VMEM scratch across KV iterations.  GQA is handled by the k/v
index_map (kv head = q head // group) — no KV repetition in HBM.  Inside
the call the (l, m) state is ``[BH, Lq, 1]``: the last two dims of a TPU
block must tile by (8, 128) or span the array, which ``(block_q, 1)``
does and a squeezed-head ``(block_q,)`` row does not.

Precision: both dots take their operands in the input dtype with f32
accumulation (bf16 inputs run at the MXU's bf16 rate, f32 inputs stay
f32), p is cast to v's dtype before p @ v, and (m, l, acc) are f32.
When K and V of a head fit VMEM, ``ops.block_sizes`` makes them one kv
block, so a head's K/V is read from HBM once and not once a q block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import pallas_interpret

NEG_INF = float("-inf")
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _kernel(
    q_ref, k_ref, v_ref, qp_ref, kp_ref, oin_ref, lin_ref, min_ref,
    o_ref, l_ref, m_ref,
    acc_s, m_s, l_s,
    *, scale: float, causal: bool, window: int | None, finalize: bool,
    n_k: int, has_state: bool, masked: bool = True,
):
    """One (head, q block, kv block) grid step.  ``masked=False`` (no
    padding, no causal or window mask: every score is finite) drops the
    per-element position compares and -inf guards; the position refs and,
    without ``has_state``, the state refs are then never read.  (l, m) are
    written where the caller passes refs for them: ``flash_mqkv`` passes
    None when ``finalize`` (a [BH, Lq, 1] f32 array pads to 128 lanes in
    HBM, as many bytes as the attention output four times over), the
    fused ring kernel always passes them."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        if has_state:
            acc_s[...] = oin_ref[...].astype(jnp.float32)
            l_s[...] = lin_ref[...].astype(jnp.float32)
            m_s[...] = min_ref[...].astype(jnp.float32)
        else:
            acc_s[...] = jnp.zeros_like(acc_s)
            l_s[...] = jnp.zeros_like(l_s)
            m_s[...] = jnp.full_like(m_s, NEG_INF)

    # MXU operands in the input dtype (bf16 runs at the MXU's bf16 rate),
    # products accumulated in f32; the scale is folded into q, a [bq, D]
    # multiply instead of a [bq, bk] one
    q = (q_ref[...].astype(jnp.float32) * scale).astype(q_ref.dtype)
    s = jax.lax.dot_general(
        q, k_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bk]

    m_prev = m_s[...]  # [bq, 1]
    l_prev = l_s[...]
    if masked:
        qp = qp_ref[...].astype(jnp.int32)  # [bq, 1]
        kp = kp_ref[...].astype(jnp.int32)  # [1, bk]
        ok = kp >= 0
        if causal:
            ok = ok & (qp >= kp)
        if window is not None:
            ok = ok & (kp > qp - window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - safe_m))
    else:
        # every score is finite, so m_new is, and exp(-inf - m_new) = 0
        # clears a fresh (or carried, fully masked) state by itself
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_s[...] = m_new
    v = v_ref[...]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_s[...] = acc_s[...] * corr + pv

    @pl.when(ki == n_k - 1)
    def _fin():
        acc = acc_s[...]
        l = l_s[...]
        if finalize:
            o_ref[...] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        else:
            o_ref[...] = acc.astype(o_ref.dtype)
        if l_ref is not None:
            l_ref[...] = l.astype(l_ref.dtype)
            m_ref[...] = m_s[...].astype(m_ref.dtype)


def vmem_limit_bytes(block_q: int, block_k: int, d: int, itemsize: int
                     ) -> int:
    """Scoped VMEM for one grid step, with room to spare: the double-
    buffered q, k, v and o tiles, the f32 (O', l, m) state and the
    [block_q, block_k] scores as f32 s, f32 p and p in v's dtype (l and m
    columns pad to 128 lanes).  At least 32 MiB, at most 100 of a v5e
    core's 128 MiB."""
    tiles = 2 * (2 * block_q * d * itemsize + 2 * block_k * d * itemsize)
    state = 2 * (block_q * d + 2 * block_q * 128) * 4
    scores = block_q * block_k * (8 + itemsize)
    return int(min(max(2 * (tiles + state + scores), 32 << 20), 100 << 20))


def flash_mqkv(
    q: jax.Array,  # [BH, Lq, D]
    k: jax.Array,  # [BHkv, Lk, D]
    v: jax.Array,
    q_pos: jax.Array,  # [Lq] int32
    k_pos: jax.Array,  # [Lk] int32, -1 = padding
    *,
    group: int = 1,  # GQA: q heads per kv head (BH = BHkv * group)
    scale: float | None = None,
    causal: bool = False,
    window: int | None = None,
    state: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    finalize: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    masked: bool = True,
    heads: int | None = None,
    keepdims: bool = False,
    interpret: bool | None = None,
):
    """Core pallas_call.  Lq % block_q == 0 and Lk % block_k == 0 required
    (ops.flash_attention pads).  Returns (o, l, m): with ``finalize`` o
    normalized and (l, m) None, else the FA2 state (O', l, m).
    ``masked=False`` promises finite scores everywhere (no ``k_pos`` of
    -1, no causal or window mask); the positions are then not read.

    ``heads`` packs the heads into the last dim instead: q and o are
    [B, Lq, heads·D], k and v [B, Lk, heads/group·D], D a multiple of 128,
    and each head's [block, D] tile is read and written in place, so the
    projections' [B, L, H·D] layout needs no transpose either way (fresh,
    finalized calls only).

    ``keepdims`` takes and returns the (l, m) state as [BH, Lq, 1], the
    kernel's own layout: on a TPU a [BH, Lq] array is laid out apart from
    it, so each conversion would be a copy of the 128-lane padded column
    (a caller that chains calls keeps the state in this form)."""
    if heads is None:
        bh, lq, d = q.shape
        bhkv, lk, _ = k.shape
        assert bh == bhkv * group, (bh, bhkv, group)
        q_at = lambda h: (h, 0)
        kv_at = lambda h: (h // group, 0)
    else:
        b, lq, hd = q.shape
        lk = k.shape[1]
        d = hd // heads
        assert d % 128 == 0 and k.shape[2] * group == hd, (q.shape, k.shape)
        assert state is None and finalize, "packed heads: fresh, finalized"
        bh = b * heads
        q_at = lambda h: (h // heads, h % heads)
        kv_at = lambda h: (h // heads, h % heads // group)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, lk, block_q, block_k)
    assert masked or not (causal or window is not None), "a mask needs masked"
    if scale is None:
        scale = d ** -0.5
    n_q, n_k = lq // block_q, lk // block_k
    has_state = state is not None

    def q_map(h, qi, ki):
        i, j = q_at(h)
        return i, qi, j

    def kv_map(h, qi, ki):
        i, j = kv_at(h)
        return i, ki, j

    q_spec = pl.BlockSpec((None, block_q, d), q_map)
    kv_spec = pl.BlockSpec((None, block_k, d), kv_map)
    lm_spec = pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, qi, 0))
    args, in_specs = [q, k, v], [q_spec, kv_spec, kv_spec]
    if masked:
        # q positions as a column: a (block_q, 1) block tiles for any
        # block_q of whole sublanes, where a (1, block_q) row needs whole
        # 128-lane tiles
        args += [q_pos.reshape(lq, 1), k_pos.reshape(1, lk)]
        in_specs += [pl.BlockSpec((block_q, 1), lambda h, qi, ki: (qi, 0)),
                     pl.BlockSpec((1, block_k), lambda h, qi, ki: (0, ki))]
    if has_state:
        o_in, l_in, m_in = state
        if not keepdims:
            l_in, m_in = l_in[..., None], m_in[..., None]
        args += [o_in, l_in, m_in]
        in_specs += [q_spec, lm_spec, lm_spec]

    def kernel(*refs):
        # absent inputs (positions when unmasked, state when fresh) are
        # not passed at all, so no dummy array is made or copied in
        refs = list(refs)
        qkv, rest = refs[:3], refs[3:]
        pos = [rest.pop(0), rest.pop(0)] if masked else [None, None]
        st = [rest.pop(0) for _ in range(3)] if has_state else [None] * 3
        outs = [rest.pop(0)] + ([None, None] if finalize
                                else [rest.pop(0), rest.pop(0)])
        _kernel(*qkv, *pos, *st, *outs, *rest, scale=scale, causal=causal,
                window=window, finalize=finalize, n_k=n_k,
                has_state=has_state, masked=masked)

    if finalize:
        out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
        out_specs = [q_spec]
    else:
        out_shape = [jax.ShapeDtypeStruct((bh, lq, d), jnp.float32),
                     jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
                     jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32)]
        out_specs = [q_spec, lm_spec, lm_spec]
    outs = pl.pallas_call(
        kernel,
        name="flash_mqkv",
        grid=(bh, n_q, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes(block_q, block_k, d,
                                              q.dtype.itemsize),
        ),
        interpret=pallas_interpret(interpret),
    )(*args)
    if finalize:
        return outs[0], None, None
    o, l, m = outs
    return (o, l, m) if keepdims else (o, l[..., 0], m[..., 0])
