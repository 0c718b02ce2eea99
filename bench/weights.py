"""Weights and text conditioning drawn from ``--seed``.

Every leaf is a function of (seed, leaf path, layer index) alone, so the
program's whole stack (one jitted call, on the device, in the served
dtype) and the reference's single layer (``layer_leaf``) hold the same
numbers.  The scales follow the DiT's own init with every leaf nudged
off it by half its fan-in scale: a fresh adaLN-zero DiT is the identity
(its adaLN modulations and output projection start at zero), which would
make any comparison vacuous.  Which leaves those are is the form's to
say: its ``INIT`` is (the path suffixes of the leaves that start at zero,
those of the residual outputs).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

COND_POOL = 8  # distinct text embeddings the traffic cycles through


def base_key(seed: int) -> jax.Array:
    """A key from all 64 bits of ``seed`` (``PRNGKey`` alone keeps 32)."""
    seed = int(seed) & (2**64 - 1)
    k = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def _std(path: str, fan_in: int, n_layers: int, init) -> float:
    zero, residual = init
    if path.endswith(zero):
        gain = 0.5
    elif path.endswith(residual):
        gain = math.sqrt(1.0 / (2 * n_layers) + 0.25)
    else:
        gain = math.sqrt(1.25)
    return gain * fan_in ** -0.5


def leaf(key: jax.Array, path: str, shape: tuple[int, ...], n_layers: int,
         init, layer: jax.Array | int | None = None) -> jax.Array:
    """One float32 leaf; ``layer`` selects a block of a stacked leaf."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if len(shape) == 1:  # norm scale / bias
        return (1.0 if path.endswith("scale") else 0.0) + 0.05 * z
    return z * _std(path, shape[0], n_layers, init)


def path_str(path) -> str:
    return "/".join(p.key for p in path)


def make_params(struct, key: jax.Array, n_layers: int, dtype, init):
    """Every leaf of the param tree ``struct`` (shapes only), in ``dtype``.
    Stacked block leaves (under ``layers``) are made one layer at a time
    so that only one layer's float32 draw is alive at once."""

    def one(path, s):
        p = path_str(path)
        if p.startswith("layers/"):
            return jax.lax.map(
                lambda i: leaf(key, p, s.shape[1:], n_layers, init,
                               i).astype(dtype),
                jnp.arange(s.shape[0]))
        return leaf(key, p, s.shape, n_layers, init).astype(dtype)

    return jax.tree_util.tree_map_with_path(one, struct)


def layer_leaf(key, path: str, shape, n_layers: int, layer: int, dtype,
               init):
    """Block ``layer`` of stacked leaf ``path``, rounded to the served
    ``dtype`` and returned in float32 (the reference's view)."""
    return leaf(key, path, shape, n_layers, init, layer).astype(
        dtype).astype(jnp.float32)


def top_leaf(key, path: str, shape, n_layers: int, dtype, init):
    return leaf(key, path, shape, n_layers, init).astype(dtype).astype(
        jnp.float32)


def cond_pool(key: jax.Array, tokens: int, width: int, dtype) -> jax.Array:
    """[COND_POOL, tokens, width] text embeddings, unit-variance."""
    k = jax.random.fold_in(key, zlib.crc32(b"cond") & 0x7FFFFFFF)
    return jax.random.normal(k, (COND_POOL, tokens, width),
                             jnp.float32).astype(
        dtype)
