"""Fused ring-step kernel vs flash_mqkv, and the ops dispatch regression.

The ring_flash kernel reuses flash_mqkv's body on the same refs, so the
attention outputs must agree *bitwise* on every configuration — random
chunk counts, k_pos = -1 padding layouts, causal/window masks, GQA, and
carried (O', l, m) state (hypothesis sweeps).  The forwarded KV
buffers must equal the inputs (the in-kernel DMA is a copy).

The dispatch regression pins kernels/ops.py's static-arg discipline: all
variant knobs (backend, fused, interpret) share ONE static tuple, so no
two lowering variants can collide on a cached trace.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import (
    flash_attention,
    flash_attention_segments,
    reset_trace_counts,
    ring_flash_step,
    trace_counts,
)
from repro.kernels.flash_mqkv import flash_mqkv


def _mk_flat(seed, bh, l, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (bh, l, d), dtype),
            jax.random.normal(ks[1], (bh, l, d), dtype),
            jax.random.normal(ks[2], (bh, l, d), dtype))


def _flash_state(*args, **kw):
    """flash_mqkv's (o, l, m) for a ring step's arguments.  A finalized
    flash_mqkv call returns no (l, m); the fused kernel still does, so
    they come from the same call unfinalized."""
    o, l, m = flash_mqkv(*args, **kw)
    if l is None:
        _, l, m = flash_mqkv(*args, **{**kw, "finalize": False})
    return o, l, m


# ---------------------------------------------------------------------------
# property sweeps: ring_flash single step == flash_mqkv, bitwise
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 4), st.integers(0, 15), st.booleans(),
       st.sampled_from([None, 24]))
def test_ring_flash_matches_flash_mqkv(n_chunks, pad, causal, window):
    """Random chunk counts / padding layouts / masks: identical (o, l, m)
    and exact forwarded buffers, with the state carried across chunks."""
    bh, d, bq, bk = 2, 16, 16, 16
    lq = 32
    lk = n_chunks * bk
    q, _, _ = _mk_flat(n_chunks * 31 + pad, bh, lq, d)
    _, k, v = _mk_flat(pad * 17 + 3, bh, lk, d)
    qp = jnp.arange(lq, dtype=jnp.int32) + lk  # q after all k (causal-safe)
    # padding layout: last `pad` k slots invalid, garbage in the data
    kp = jnp.where(jnp.arange(lk) < lk - min(pad, lk - 1),
                   jnp.arange(lk), -1).astype(jnp.int32)
    k = jnp.where((kp < 0)[None, :, None], 999.0, k)
    v = jnp.where((kp < 0)[None, :, None], 999.0, v)

    state = None
    for c in range(n_chunks):
        sl = slice(c * bk, (c + 1) * bk)
        args = (q, k[:, sl], v[:, sl], qp, kp[sl])
        kw = dict(causal=causal, window=window, state=state,
                  finalize=c == n_chunks - 1, block_q=bq, block_k=bk,
                  interpret=True)
        ref = _flash_state(*args, **kw)
        (o, l, m), (kf, vf) = ring_flash_step(*args, **kw)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(ref[0]))
        np.testing.assert_array_equal(np.asarray(l), np.asarray(ref[1]))
        np.testing.assert_array_equal(np.asarray(m), np.asarray(ref[2]))
        np.testing.assert_array_equal(np.asarray(kf), np.asarray(k[:, sl]))
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(v[:, sl]))
        state = ref if c < n_chunks - 1 else None


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([1, 2, 4]), st.booleans())
def test_ring_flash_gqa_groups(group, causal):
    bh_kv, d = 2, 16
    q, _, _ = _mk_flat(11, bh_kv * group, 32, d)
    _, k, v = _mk_flat(12, bh_kv, 32, d)
    pos = jnp.arange(32, dtype=jnp.int32)
    ref = _flash_state(q, k, v, pos, pos, group=group, causal=causal,
                       block_q=16, block_k=16, interpret=True)
    (o, l, m), _ = ring_flash_step(q, k, v, pos, pos, group=group,
                                   causal=causal, block_q=16, block_k=16,
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(l), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(ref[2]))


def test_segments_fused_matches_unfused():
    """flash_attention_segments through the fused kernel == plain kernel."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 32, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    kp = jnp.arange(64, dtype=jnp.int32)
    segs = [(k[:, :32], v[:, :32], kp[:32]), (k[:, 32:], v[:, 32:], kp[32:])]
    qp = jnp.arange(32) + 32
    a = flash_attention_segments(q, segs, q_pos=qp, causal=True,
                                 block_q=16, block_k=16, interpret=True,
                                 fused=False)
    b = flash_attention_segments(q, segs, q_pos=qp, causal=True,
                                 block_q=16, block_k=16, interpret=True,
                                 fused=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend,fused", [("pallas", False),
                                           ("pallas", True),
                                           ("xla", False)])
def test_flash_attention_backends_agree(backend, fused):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 32))
    k = jax.random.normal(ks[1], (2, 48, 2, 32))
    v = jax.random.normal(ks[2], (2, 48, 2, 32))
    ref = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True, backend=backend, fused=fused)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# dispatch regression: variants never collide on a cached trace
# ---------------------------------------------------------------------------

def test_dispatch_traces_once_per_variant():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    reset_trace_counts()

    variants = [
        dict(backend="pallas", fused=False),
        dict(backend="pallas", fused=True),
        dict(backend="xla", fused=False),
    ]
    for kw in variants:
        for _ in range(3):  # repeats must hit the cache, not re-trace
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                            interpret=True, **kw)
    counts = trace_counts()
    # one distinct static key per variant — a collision would show up as
    # fewer keys (variants sharing a trace) or counts > 1 (re-tracing)
    assert len(counts) == len(variants), counts
    assert all(n == 1 for n in counts.values()), counts
    keys = set(counts)
    assert {(kk[-2], kk[-1]) for kk in keys} == {
        ("pallas", False), ("pallas", True), ("xla", False)}
