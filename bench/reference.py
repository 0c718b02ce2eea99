"""What every plain float32 reference of a served DiT shares, in
``jax.numpy`` only.

It imports nothing of the program.  An architecture's own maths lives in
its form module (``bench/forms/<form>.py``): its weight shapes, its
embedding of [text ; latent] tokens, its block and its final layer.  This
library drives them the same way for every form: weights come from
``bench.weights`` layer by layer, so the reference holds one block at a
time; attention runs in blocks of query rows, so no score tensor is
larger than [B, H, block, L]; given several chips it splits each block's
token rows among them, and gives the two guidance branches half of the
chips each; the sampler is the flow-matching Euler step x <- x - dt * v
with optional classifier-free guidance v = v_u + g (v_c - v_u) against an
all-zero text embedding.

``mode="fp8"`` is the control: the same maths with every matmul operand
rounded to float8_e4m3fn (per-tensor scale), the step below the served
bfloat16.  ``run.py`` never calls it; ``calibrate.py`` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0  # largest finite float8_e4m3fn


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def top_weights(key, shapes: tuple, n_layers: int, dtype: str, init):
    """The leaves outside the blocks: ``shapes`` is ((path, shape), ...)."""
    return {p: W.top_leaf(key, p, s, n_layers, dtype, init)
            for p, s in shapes}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def block_weights(key, shapes: tuple, n_layers: int, dtype: str, init,
                  layer):
    """Block ``layer`` of the stacked leaves under ``layers/``."""
    return {p: W.layer_leaf(key, "layers/" + p, s, n_layers, layer, dtype,
                            init)
            for p, s in shapes}


def _q8(x):
    """Round to float8_e4m3fn with a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec: str, a, b, mode: str):
    """A matmul at ``highest`` precision; float8 operands under ``fp8``."""
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def q_block(length: int, target: int = 512) -> int:
    """Largest divisor of ``length`` not above ``target``."""
    return max(b for b in range(1, min(length, target) + 1)
               if length % b == 0)


def blocked_attention(q, k, v, mode: str):
    """softmax(q k^T / sqrt(hd)) v for q [B, Lq, H, hd] against k, v
    [B, L, H, hd], a block of query rows at a time; [B, Lq, H * hd]."""
    b, lq, heads, hd = q.shape
    blk = q_block(lq)
    qb = q.reshape(b, lq // blk, blk, heads, hd)

    def one(qi):  # [b, blk, H, hd] against every key
        s = mm("bqhd,bkhd->bhqk", qi, k, mode) * hd ** -0.5
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v, mode)

    o = jax.lax.map(one, jnp.moveaxis(qb, 1, 0))  # [nb, b, blk, H, hd]
    return jnp.moveaxis(o, 0, 1).reshape(b, lq, heads * hd)


@jax.jit
def _join(parts):
    return jnp.concatenate(parts, axis=1)


def velocity(form, key, n, x_lat, cond, t: float, mode: str = "f32",
             devices=None):
    """``form``'s v(x_t, t) for latents [B, T, channels] and text
    [B, text_tokens, text_width].  With several ``devices`` each block's
    token rows are split among them (every chip computes all keys and
    values) and joined again.  ``form`` gives ``INIT``,
    ``top_shapes(n)``, ``block_shapes(n)`` and the jitted ``embed``,
    ``block`` and ``final``."""
    devices = list(devices or [jax.devices()[0]])
    parts = len(devices)
    keys = [jax.device_put(key, d) for d in devices]
    top = top_weights(keys[0], form.top_shapes(n), n.layers, n.dtype,
                      form.INIT)
    x, t_emb = form.embed(top, *jax.device_put((x_lat, cond), devices[0]),
                          jnp.float32(t), mode)
    if x.shape[1] % parts:
        raise ValueError(f"{x.shape[1]} rows do not split {parts} ways")
    xs = [jax.device_put(x, d) for d in devices]
    ts = [jax.device_put(t_emb, d) for d in devices]
    shapes = form.block_shapes(n)
    for i in range(n.layers):
        outs = [form.block(block_weights(k, shapes, n.layers, n.dtype,
                                         form.INIT, jnp.int32(i)),
                           xd, td, n, mode, j, parts)
                for j, (k, xd, td) in enumerate(zip(keys, xs, ts))]
        xs = [_join([jax.device_put(o, d) for o in outs]) for d in devices]
    return form.final(top, xs[0], ts[0], n.text_tokens, mode)


def initial_noise(rid: int, tokens: int, channels: int,
                  dtype: str) -> jax.Array:
    """A served request's starting latents, as the server draws them:
    ``normal(fold_in(PRNGKey(0), rid), [tokens, channels])`` in the
    served dtype."""
    k = jax.random.fold_in(jax.random.PRNGKey(0), rid)
    return jax.random.normal(k, (tokens, channels),
                             jnp.dtype(dtype)).astype(jnp.float32)


def sample(form, key, n, x0, cond, steps: int, guidance: float = 1.0,
           mode: str = "f32", devices=None):
    """The Euler sampler of ``form`` from ``x0`` [T, channels] with text
    ``cond`` [C, width] over ``steps`` uniform steps from t = 1.  With
    guidance the conditional and unconditional passes each take half of
    ``devices`` (default: the first device), side by side."""
    devices = list(devices or [jax.devices()[0]])
    guided = guidance != 1.0
    if guided and len(devices) > 1:
        half = len(devices) // 2
        dev_c, dev_u = devices[:half], devices[half:2 * half]
    else:
        dev_c = dev_u = devices
    x = jax.device_put(jnp.asarray(x0, jnp.float32)[None], dev_c[0])
    c = jnp.asarray(cond, jnp.float32)[None]
    dt = 1.0 / steps
    for i in range(steps):
        t = 1.0 - i * dt
        v = velocity(form, key, n, x, c, t, mode, dev_c)
        if guided:
            v_u = velocity(form, key, n, x, jnp.zeros_like(c), t, mode,
                           dev_u)
            v_u = jax.device_put(v_u, dev_c[0])
            v = v_u + guidance * (v - v_u)
        x = x - dt * v
    return x[0]


def rel_err(got, want, start) -> float:
    """|got - want| / |want - start|: the error against the distance the
    reference moved the latents, on the host in float64."""
    got, want, start = (np.asarray(a, np.float64) for a in (got, want, start))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want - start))
