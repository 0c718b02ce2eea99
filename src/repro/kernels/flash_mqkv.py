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
"""
from __future__ import annotations

import functools

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
    n_k: int, has_state: bool,
):
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

    q = q_ref[...].astype(jnp.float32)  # [bq, D]
    k = k_ref[...].astype(jnp.float32)  # [bk, D]
    v = v_ref[...].astype(jnp.float32)
    qp = qp_ref[...].astype(jnp.int32)[0]  # [bq]
    kp = kp_ref[...].astype(jnp.int32)[0]  # [bk]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bk]

    ok = (kp >= 0)[None, :]
    if causal:
        ok = ok & (qp[:, None] >= kp[None, :])
    if window is not None:
        ok = ok & (kp[None, :] > qp[:, None] - window)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_s[...]  # [bq, 1]
    l_prev = l_s[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m)
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - safe_m))
    l_s[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_s[...] = m_new
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
        l_ref[...] = l.astype(l_ref.dtype)
        m_ref[...] = m_s[...].astype(m_ref.dtype)


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
    interpret: bool | None = None,
):
    """Core pallas_call.  Lq % block_q == 0 and Lk % block_k == 0 required
    (ops.flash_attention pads).  Returns (o, l, m); o normalized iff
    ``finalize``."""
    bh, lq, d = q.shape
    bhkv, lk, _ = k.shape
    assert bh == bhkv * group, (bh, bhkv, group)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, lk, block_q, block_k)
    if scale is None:
        scale = d ** -0.5
    n_q, n_k = lq // block_q, lk // block_k
    has_state = state is not None

    qp2 = q_pos.reshape(1, lq)
    kp2 = k_pos.reshape(1, lk)
    if state is None:
        # dummies (never read — has_state=False skips them); keep them tiny
        o_in = jnp.zeros((bh, block_q, d), jnp.float32)
        l_in = jnp.zeros((bh, block_q, 1), jnp.float32)
        m_in = jnp.zeros((bh, block_q, 1), jnp.float32)
        oin_spec = pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, 0, 0))
        lin_spec = pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, 0, 0))
    else:
        o_in, l_in, m_in = state
        l_in, m_in = l_in[..., None], m_in[..., None]
        oin_spec = pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, qi, 0))
        lin_spec = pl.BlockSpec((None, block_q, 1),
                                lambda h, qi, ki: (h, qi, 0))

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        finalize=finalize, n_k=n_k, has_state=has_state,
    )
    out_shape = (
        jax.ShapeDtypeStruct((bh, lq, d), q.dtype if finalize else jnp.float32),
        jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
    )
    grid = (bh, n_q, n_k)
    o, l, m = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda h, qi, ki, g=group: (h // g, ki, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda h, qi, ki, g=group: (h // g, ki, 0)),
            pl.BlockSpec((1, block_q), lambda h, qi, ki: (0, qi)),
            pl.BlockSpec((1, block_k), lambda h, qi, ki: (0, ki)),
            oin_spec,
            lin_spec,
            lin_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, qi, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(interpret),
    )(q, k, v, qp2, kp2, o_in, l_in, m_in)
    return o, l[..., 0], m[..., 0]
