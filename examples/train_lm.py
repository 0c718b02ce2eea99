"""End-to-end driver: train a ~100M-param qwen2-family model for a few
hundred steps on synthetic data with the SwiftFusion SP attention in the
loss path (deliverable (b) end-to-end driver).

Runs on whatever devices exist; on this container that is 1 CPU device
(strategy degrades to the single-device oracle path, which is exactly what
the paper's methods do at SP=1).  Pass --steps to shorten.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import get_config
from repro.configs.shapes import InputShape
from repro.core import SPConfig
from repro.launch.mesh import make_host_mesh
from repro.train import AdamWConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="experiments/ckpt/train_lm")
    args = ap.parse_args()

    # ~100M-parameter qwen2-family variant (95M: 12L d=768 ff=2304 v=16k)
    cfg = dataclasses.replace(
        get_config("qwen2-1.5b"),
        n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2304,
        vocab=16384, dtype="float32", sharding_overrides=(),
    )
    n_params = cfg.params_dense_estimate()
    print(f"model: {n_params / 1e6:.1f}M params, "
          f"{cfg.n_layers}L d={cfg.d_model}")

    mesh = make_host_mesh(model=len(jax.devices()))
    sp = SPConfig(strategy="swift_torus" if len(jax.devices()) > 1 else "full",
                  sp_axes=("model",), batch_axes=("data",))
    shape = InputShape("train_demo", args.seq, args.batch, "training")
    tr = Trainer(cfg, mesh, sp, shape,
                 opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=20,
                                     total_steps=args.steps),
                 ckpt_path=args.ckpt)
    params, history = tr.run(args.steps, log_every=20)
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"\nloss {first:.3f} -> {last:.3f} over {args.steps} steps "
          f"({'OK: decreased' if last < first else 'WARN: did not decrease'})")


if __name__ == "__main__":
    main()
