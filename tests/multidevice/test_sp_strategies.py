"""SP strategies ≡ single-device oracle on a (2,2,2) mesh (8 fake devices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MaskSpec,
    SPConfig,
    decode_attention,
    reference_attention,
    sp_attention,
)

B, L, HQ, HKV, D = 2, 32, 8, 4, 16


@pytest.fixture(scope="module")
def qkv(rng):
    kq, kk, kv = jax.random.split(rng, 3)
    return (jax.random.normal(kq, (B, L, HQ, D)),
            jax.random.normal(kk, (B, L, HKV, D)),
            jax.random.normal(kv, (B, L, HKV, D)))


@pytest.mark.parametrize("strategy", ["ring", "ulysses", "usp", "swift",
                                      "swift_torus"])
@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 12), (False, 9)])
def test_strategy_matches_oracle(strategy, causal, window, qkv, mesh8):
    q, k, v = qkv
    cfg = SPConfig(strategy=strategy, sp_axes=("pod", "model"),
                   batch_axes=("data",))
    ref = reference_attention(q, k, v,
                              mask=MaskSpec(causal=causal, window=window))
    out = jax.jit(lambda q, k, v: sp_attention(
        q, k, v, mesh=mesh8, cfg=cfg, causal=causal, window=window))(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("strategy", ["usp", "swift", "swift_torus"])
def test_sp_over_all_three_axes(strategy, qkv, mesh8):
    """long_500k-style: sequence sharded over the whole mesh."""
    q, k, v = qkv
    cfg = SPConfig(strategy=strategy, sp_axes=("pod", "data", "model"),
                   batch_axes=None)
    ref = reference_attention(q, k, v, mask=MaskSpec(causal=True))
    out = jax.jit(lambda q, k, v: sp_attention(
        q, k, v, mesh=mesh8, cfg=cfg, causal=True))(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_gqa_limits_ulysses_degree(qkv, mesh8):
    """kv=1 head ⇒ planner must fall back to pure ring; still correct."""
    q, k, v = qkv
    k1, v1 = k[:, :, :1], v[:, :, :1]
    cfg = SPConfig(strategy="swift_torus", sp_axes=("pod", "model"),
                   batch_axes=("data",))
    ref = reference_attention(q, k1, v1, mask=MaskSpec(causal=True))
    out = jax.jit(lambda q, k, v: sp_attention(
        q, k, v, mesh=mesh8, cfg=cfg, causal=True))(q, k1, v1)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_decode_attention_distributed(qkv, mesh8):
    q, k, v = qkv
    cur = 21
    kc = jnp.zeros((B, L, HKV, D)).at[:, :cur].set(k[:, :cur])
    vc = jnp.zeros((B, L, HKV, D)).at[:, :cur].set(v[:, :cur])
    cfg = SPConfig(strategy="swift", sp_axes=("pod", "model"),
                   batch_axes=("data",))
    o, kc2, vc2 = jax.jit(lambda *a: decode_attention(
        *a, mesh=mesh8, cfg=cfg))(q[:, cur:cur + 1], kc, vc,
                                  k[:, cur:cur + 1], v[:, cur:cur + 1],
                                  jnp.int32(cur))
    ref = reference_attention(q[:, cur:cur + 1], k[:, :cur + 1], v[:, :cur + 1])
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(kc2[:, cur], k[:, cur], rtol=1e-6)


def test_decode_attention_windowed(qkv, mesh8):
    q, k, v = qkv
    cur, win = 25, 8
    kc = jnp.zeros((B, L, HKV, D)).at[:, :cur].set(k[:, :cur])
    vc = jnp.zeros((B, L, HKV, D)).at[:, :cur].set(v[:, :cur])
    cfg = SPConfig(strategy="swift", sp_axes=("pod", "model"),
                   batch_axes=("data",))
    o, _, _ = jax.jit(lambda *a: decode_attention(
        *a, mesh=mesh8, cfg=cfg, window=win))(q[:, cur:cur + 1], kc, vc,
                                              k[:, cur:cur + 1],
                                              v[:, cur:cur + 1], jnp.int32(cur))
    lo = cur + 1 - win
    ref = reference_attention(q[:, cur:cur + 1], k[:, lo:cur + 1],
                              v[:, lo:cur + 1])
    np.testing.assert_allclose(o, ref, rtol=1e-4, atol=1e-4)
