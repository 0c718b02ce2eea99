"""Pallas TPU kernel: chunked RWKV6 (Finch) WKV scan.

The SSM counterpart of flash_mqkv: grid (batch·heads, n_chunks) with the
chunk axis sequential ("arbitrary"), carrying the recurrent state
S [N, N] in VMEM scratch across chunks — the same carried-running-state
pattern Algorithm 2 uses for (m, l), applied to the linear recurrence

    S_t = diag(w_t) S_{t-1} + k_t^T v_t
    o_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

Within a chunk the recurrence is evaluated in matmul form (GLA-style
cumulative-decay trick, MXU-friendly):

    o = ((r·D₋) (k/D)^T ⊙ tril) v + diag(r·u·k) v + (r·D₋) S_in

Decays are clipped to [EPS, 1] so the cumulative-product normalisation
stays bounded (decays ≤ 1 by construction in RWKV6).

Validated in interpret mode against models/ssm.rwkv6_chunk_scan and the
naive sequential recurrence (tests/test_kernels_rwkv.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import pallas_interpret

EPS = 1e-6
DEFAULT_CHUNK = 64


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scratch, *, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scratch[...] = jnp.zeros_like(s_scratch)

    r = r_ref[...].astype(jnp.float32)  # [c, N]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    w = jnp.clip(w_ref[...].astype(jnp.float32), EPS, 1.0)
    u = u_ref[...].astype(jnp.float32)  # [1, N]

    logw = jnp.log(w)
    logD = jnp.cumsum(logw, axis=0)  # inclusive cumulative decay
    D = jnp.exp(logD)
    Dm1 = jnp.exp(logD - logw)  # exclusive (D_{t-1})
    c = r.shape[0]

    r_sc = r * Dm1  # r_t ⊙ D_{t-1}
    k_sc = k / D    # k_s / D_s
    att = jax.lax.dot_general(r_sc, k_sc, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [c, c]
    tri = jnp.tril(jnp.ones((c, c), jnp.float32), k=-1)
    att = att * tri
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # r_t·(u ⊙ k_t)
    o = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o = o + diag * v
    # cross-chunk: contribution of the carried state
    s_in = s_scratch[...]
    o = o + jax.lax.dot_general(r_sc, s_in, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)

    # state update: S = a_c ⊙ S_in + sum_s (a_c / D_s ⊙ k_s) ⊗ v_s
    a_c = D[-1]  # [N]
    k_tail = k_sc * a_c[None, :]
    s_new = a_c[:, None] * s_in + jax.lax.dot_general(
        k_tail, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scratch[...] = s_new


def rwkv6_wkv(
    r: jax.Array,  # [BH, L, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0, 1]
    u: jax.Array,  # [BH, N] per-head bonus
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool | None = None,
) -> jax.Array:
    """Returns o [BH, L, N] (f32)."""
    bh, l, n = r.shape
    c = min(chunk, l)
    assert l % c == 0, (l, c)
    n_chunks = l // c
    u2 = u.reshape(bh, 1, n)

    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    spec = pl.BlockSpec((None, c, n), lambda h, ci: (h, ci, 0))
    uspec = pl.BlockSpec((None, 1, n), lambda h, ci: (h, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[spec, spec, spec, spec, uspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bh, l, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=pallas_interpret(interpret),
    )(r, k, v, w, u2)
