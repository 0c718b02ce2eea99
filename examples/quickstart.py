"""Quickstart: SwiftFusion SP attention in 40 lines.

Runs every SP strategy on a small attention problem over however many
devices are available (fake 8 CPU devices here) and checks them against
the single-device oracle — then shows the paper's planner picking
(P_u, P_r) for a real architecture.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.core import MaskSpec, SPConfig, plan, reference_attention, sp_attention


def main():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (2, 64, 8, 32))   # [B, L, Hq, D]
    k = jax.random.normal(kk, (2, 64, 4, 32))   # GQA: 4 KV heads
    v = jax.random.normal(kv, (2, 64, 4, 32))

    ref = reference_attention(q, k, v, mask=MaskSpec(causal=True))
    for strategy in ("ring", "ulysses", "usp", "swift", "swift_torus"):
        cfg = SPConfig(strategy=strategy, sp_axes=("pod", "model"),
                       batch_axes=("data",))
        out = jax.jit(lambda q, k, v: sp_attention(
            q, k, v, mesh=mesh, cfg=cfg, causal=True))(q, k, v)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"{strategy:12s} max|Δ| vs oracle = {err:.2e}")

    print("\nplanner on the production SP group (2 pods × 16 chips):")
    for arch, hq, hkv in (("qwen2-1.5b", 12, 2), ("arctic-480b", 56, 8),
                          ("flux-12b", 24, 24)):
        p = plan(2, 16, hq, hkv)
        print(f"  {arch:14s} Hq={hq:3d} Hkv={hkv:3d} -> "
              f"P_u={p.p_ulysses:2d} (inter-pod Ulysses/Torus), "
              f"P_r={p.p_ring:2d} (intra-pod Ring)")


if __name__ == "__main__":
    main()
