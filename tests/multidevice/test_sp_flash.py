"""SP > 1 stage attends through the flash kernel ≡ the single-device oracle.

The platform test of the lowering's predicate is reported as a TPU's, so
``sp_attention`` takes the kernel lowering (``+flash``); the kernel itself
still runs interpreted on the CPU devices.  ``swift_torus`` at P_u 4,
P_r 1 (the video cell's plan), and ``usp`` and ``ring`` at P_r > 1.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core import MaskSpec, SPConfig, reference_attention, sp_attention
from repro.core import strategy
from repro.core.strategy import attention_lowering, resolve_layout

B, L, HQ, D = 2, 44, 8, 64  # 11 rows a device: padded blocks


@pytest.fixture
def tpu_lowering(monkeypatch):
    monkeypatch.setattr(strategy, "pallas_interpret", lambda: False)


def _qkv(hkv):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(hkv), 3)
    return (jax.random.normal(kq, (B, L, HQ, D)),
            jax.random.normal(kk, (B, L, hkv, D)),
            jax.random.normal(kv, (B, L, hkv, D)))


def _plan(strategy_name, mesh8):
    """Mesh, config and KV heads: four devices, one Ulysses group for the
    torus; for usp two KV heads cap the Ulysses degree at 2, so two
    groups of two ring the KV."""
    if strategy_name == "swift_torus":
        mesh = make_mesh((1, 4), ("data", "model"),
                         devices=jax.devices()[:4])
        return mesh, SPConfig(strategy=strategy_name, sp_axes=("model",),
                              batch_axes=("data",)), 4
    return mesh8, SPConfig(strategy=strategy_name, sp_axes=("pod", "model"),
                           batch_axes=("data",)), 2


@pytest.mark.parametrize("strategy_name,p_u,p_r", [("swift_torus", 4, 1),
                                                   ("usp", 2, 2),
                                                   ("ring", 1, 4)])
@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (False, 9)])
def test_flash_stages_match_oracle(tpu_lowering, strategy_name, p_u, p_r,
                                   causal, window, mesh8):
    mesh, cfg, hkv = _plan(strategy_name, mesh8)
    q, k, v = _qkv(hkv)
    layout = resolve_layout(cfg, mesh, HQ, hkv)
    assert (layout.p_ulysses, layout.p_ring) == (p_u, p_r)
    assert attention_lowering(cfg, mesh, L, D, window) == (
        f"{strategy_name}+flash")
    f = lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=cfg,
                                     causal=causal, window=window)
    assert "pallas_call" in str(jax.make_jaxpr(f)(q, k, v))
    ref = reference_attention(q, k, v,
                              mask=MaskSpec(causal=causal, window=window))
    np.testing.assert_allclose(jax.jit(f)(q, k, v), ref, rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("strategy_name", ["swift_torus", "usp"])
def test_flash_stages_differentiate_as_the_oracle(tpu_lowering,
                                                  strategy_name, mesh8):
    """A Pallas call has no transpose rule: the stage kernel's gradient is
    the oracle's, so training at SP > 1 still differentiates."""
    mesh, cfg, hkv = _plan(strategy_name, mesh8)
    q, k, v = _qkv(hkv)
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)
    flash = lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=cfg,
                                         causal=True)
    oracle = lambda q, k, v: reference_attention(
        q, k, v, mask=MaskSpec(causal=True))
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)
