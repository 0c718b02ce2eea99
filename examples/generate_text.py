"""Serve an assigned LM arch with batched requests through the AR engine:
continuous batching over a sequence-sharded KV cache.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/generate_text.py
"""
import dataclasses
import os
import sys

if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.compat import make_mesh
from repro.configs import get_reduced
from repro.core import SPConfig
from repro.models import get_model
from repro.serving import ARRequest, ARServer


def main():
    cfg = dataclasses.replace(get_reduced("qwen2-1.5b"), dtype="float32")
    bundle = get_model(cfg)
    params, _ = bundle.init(cfg, jax.random.PRNGKey(0), 1)

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    # decode shards the KV cache over (pod, model); 4 batch slots over data
    sp = SPConfig(strategy="swift", sp_axes=("pod", "model"),
                  batch_axes=("data",))
    srv = ARServer(params, cfg, mesh, sp, batch_slots=4, max_len=64)

    prompts = {
        1: [3, 1, 4, 1, 5],
        2: [2, 7, 1, 8],
        3: [9, 9, 9],
        4: [11],
        5: [5, 4, 3, 2, 1],
        6: [42, 42],
    }
    for rid, p in prompts.items():
        srv.submit(ARRequest(rid=rid, prompt=jnp.asarray(p, jnp.int32),
                             max_new_tokens=8))
    results = srv.serve()
    for rid in sorted(results):
        print(f"request {rid}: prompt={prompts[rid]} -> {results[rid]}")
    print(f"\nserved {len(results)} requests; KV cache sequence-sharded over "
          f"(pod × model) = {mesh.shape['pod'] * mesh.shape['model']} ways")


if __name__ == "__main__":
    main()
