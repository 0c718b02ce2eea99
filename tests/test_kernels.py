"""Pallas flash_mqkv kernel vs pure-jnp oracle (interpret mode on CPU).

Sweeps shapes / dtypes / masks / GQA groups / multi-segment merges, the
blocks derived from the shapes, and the SP=1 choice between the kernel
and the oracle.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MaskSpec, SPConfig, reference_attention
from repro.core import strategy
from repro.core.strategy import attention_lowering, sp_attention
from repro.kernels import flash_attention, flash_attention_segments
from repro.kernels.flash_mqkv import flash_mqkv
from repro.kernels.ops import KV_VMEM_BYTES, SCORE_ELEMS, block_sizes
from repro.kernels.ref import flash_attention_ref


def _mk(key, b, lq, lk, hq, hkv, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, lq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, lk, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, lk, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", [
    (1, 16, 16, 1, 1, 16),
    (2, 64, 64, 4, 2, 32),
    (1, 128, 256, 8, 8, 64),
    (2, 48, 80, 6, 3, 128),   # non-multiple of block -> padding path
])
@pytest.mark.parametrize("causal,window", [(False, None), (True, None), (True, 20)])
def test_kernel_shape_sweep(shape, causal, window):
    b, lq, lk, hq, hkv, d = shape
    if causal and lq != lk:
        lk = lq  # causal comparison needs aligned positions
    q, k, v = _mk(jax.random.PRNGKey(0), b, lq, lk, hq, hkv, d, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_k=32, interpret=True)
    ref = reference_attention(q, k, v, mask=MaskSpec(causal=causal, window=window))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_kernel_dtype_sweep(dtype, tol):
    q, k, v = _mk(jax.random.PRNGKey(1), 2, 64, 64, 4, 2, 64, dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), mask=MaskSpec(causal=True))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("group", [1, 2, 4])
def test_kernel_gqa_groups(group):
    hkv = 2
    q, k, v = _mk(jax.random.PRNGKey(2), 2, 32, 32, hkv * group, hkv, 32,
                  jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True)
    ref = reference_attention(q, k, v, mask=MaskSpec(causal=True))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_kernel_positions_discontiguous():
    """Chunks anywhere in memory: exact masks from global position arrays."""
    key = jax.random.PRNGKey(3)
    q, k, v = _mk(key, 1, 32, 32, 2, 2, 32, jnp.float32)
    # global positions: q at [100, 132), k split across two far-apart ranges
    q_pos = jnp.arange(32) + 100
    k_pos = jnp.concatenate([jnp.arange(16), jnp.arange(16) + 110])
    out = flash_attention(q, k, v, q_pos, k_pos, causal=True,
                          block_q=16, block_k=16, interpret=True)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(2, 32, 32),
        k.transpose(0, 2, 1, 3).reshape(2, 32, 32),
        v.transpose(0, 2, 1, 3).reshape(2, 32, 32),
        q_pos, k_pos, causal=True)
    np.testing.assert_allclose(
        out, ref.reshape(1, 2, 32, 32).transpose(0, 2, 1, 3), rtol=2e-5, atol=2e-5)


def test_kernel_state_carry_matches_single_call():
    """Algorithm 2's fused merge: two calls with carried (O', l, m) ==
    one call over the concatenated KV."""
    key = jax.random.PRNGKey(4)
    q, k, v = _mk(key, 1, 32, 64, 2, 2, 32, jnp.float32)
    kp = jnp.arange(64, dtype=jnp.int32)
    segs = [(k[:, :32], v[:, :32], kp[:32]), (k[:, 32:], v[:, 32:], kp[32:])]
    out = flash_attention_segments(q, segs, q_pos=jnp.arange(32) + 32,
                                   causal=True, block_q=16, block_k=16,
                                   interpret=True)
    full = flash_attention(q, k, v, jnp.arange(32) + 32, kp, causal=True,
                           block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(out, full, rtol=2e-5, atol=2e-5)


def test_kernel_segment_order_invariance():
    key = jax.random.PRNGKey(5)
    q, k, v = _mk(key, 1, 32, 96, 2, 1, 32, jnp.float32)
    kp = jnp.arange(96, dtype=jnp.int32)
    segs = [(k[:, i:i + 32], v[:, i:i + 32], kp[i:i + 32]) for i in (0, 32, 64)]
    a = flash_attention_segments(q, segs, q_pos=jnp.arange(32) + 64,
                                 causal=True, interpret=True)
    b = flash_attention_segments(q, segs[::-1], q_pos=jnp.arange(32) + 64,
                                 causal=True, interpret=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_kernel_padding_masked():
    """k_pos = -1 marks padding: result identical to the unpadded call."""
    key = jax.random.PRNGKey(6)
    q, k, v = _mk(key, 1, 16, 48, 2, 2, 32, jnp.float32)
    out_full = flash_attention(q, k[:, :40], v[:, :40],
                               jnp.arange(16), jnp.arange(40),
                               block_q=16, block_k=16, interpret=True)
    kp = jnp.where(jnp.arange(48) < 40, jnp.arange(48), -1)
    kk = k.at[:, 40:].set(999.0)  # garbage in padded slots must not leak
    vv = v.at[:, 40:].set(999.0)
    out_pad = flash_attention(q, kk, vv, jnp.arange(16), kp,
                              block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(out_pad, out_full, rtol=2e-5, atol=2e-5)


def test_kernel_unnormalized_state_output():
    """finalize=False returns FA2-style (O', l, m) mergeable state."""
    key = jax.random.PRNGKey(7)
    b, l, h, d = 1, 32, 2, 32
    q, k, v = _mk(key, b, l, l, h, h, d, jnp.float32)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    pos = jnp.arange(l, dtype=jnp.int32)
    o, lsum, m = flash_mqkv(qf, kf, vf, pos, pos, finalize=False,
                            block_q=16, block_k=16, interpret=True)
    o_ref, l_ref, m_ref = flash_attention_ref(qf, kf, vf, pos, pos,
                                              finalize=False)
    np.testing.assert_allclose(o, o_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(lsum, l_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(m, m_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [
    (1, 256, 2, 2, 128),   # one block each way
    (1, 2560, 2, 2, 128),  # q blocks of 640 rows against one kv block
    (2, 512, 4, 2, 128),   # GQA, heads packed in the lanes
    (1, 512, 2, 2, 64),    # head_dim 64: heads flattened
    (1, 300, 2, 2, 128),   # 300 keys: one block spanning them, unpadded
])
def test_kernel_bf16_shape_derived_blocks(shape):
    """bf16 MXU operands, f32 softmax state, blocks from the shapes: the
    no-mask path and one padded length against the float32 oracle."""
    b, l, hq, hkv, d = shape
    q, k, v = _mk(jax.random.PRNGKey(8), b, l, l, hq, hkv, d, jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    ref = reference_attention(*(x.astype(jnp.float32) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, rtol=2e-2,
                               atol=2e-2)


@pytest.mark.parametrize("state", [False, True])
def test_kernel_unmasked_path_matches_masked(state):
    """``masked=False`` skips the compares and -inf guards, fresh or with
    a carried state, and changes no number."""
    b, l, h, d = 1, 64, 2, 32
    q, k, v = _mk(jax.random.PRNGKey(9), b, l, l, h, h, d, jnp.float32)
    qf, kf, vf = (x.transpose(0, 2, 1, 3).reshape(b * h, l, d)
                  for x in (q, k, v))
    pos = jnp.arange(l, dtype=jnp.int32)
    carried = (flash_mqkv(qf, kf, vf, pos, pos, finalize=False, block_q=16,
                          block_k=16, interpret=True) if state else None)
    outs = [flash_mqkv(qf, kf, vf, pos, pos, state=carried, finalize=False,
                       block_q=16, block_k=32, masked=masked, interpret=True)
            for masked in (True, False)]
    for got, want in zip(*outs):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("length", [1280, 2560, 4352])
def test_block_sizes_from_shape(length):
    """The served lengths tile without padding: one kv block spanning the
    length, q blocks that divide it, scores within the VMEM budget."""
    bq, bk = block_sizes(length, length, 128, 2)
    assert bk == length and length % bq == 0 and bq % 16 == 0
    assert bq * bk <= SCORE_ELEMS


def test_block_sizes_pad_without_a_divisor():
    """A length with no fitting divisor keeps K one unpadded block and
    pads q to the fewest whole blocks within the score budget; K and V
    too large for VMEM split into blocks."""
    bq, bk = block_sizes(4100, 4100, 128, 2)  # 4100 = 4 * 25 * 41
    cap = SCORE_ELEMS // bk // 16 * 16
    assert bk == 4100 and bq <= cap and bq % 16 == 0
    assert -(-4100 // bq) == -(-4100 // cap)  # as few blocks as the cap
    assert -(-4100 // bq) * bq - 4100 < 16 * -(-4100 // bq)
    long = 65536  # K and V of a head, double-buffered: 64 MiB
    bq, bk = block_sizes(long, long, 128, 2)
    assert bk < long and long % bk == 0 and 4 * bk * 128 * 2 <= KV_VMEM_BYTES


# ---------------------------------------------------------------------------
# dispatch at SP=1: the flash kernel on a TPU, the oracle elsewhere
# ---------------------------------------------------------------------------

def _mesh(data, model):
    return types.SimpleNamespace(shape={"data": data, "model": model})


@pytest.mark.parametrize("backend,q_len,head_dim,window,want", [
    ("tpu", 1280, 128, None, "flash"),   # 1 x 1280 x 24 x 128 self-attention
    ("cpu", 1280, 128, None, "reference"),  # the CPU keeps the oracle
    ("tpu", 1, 128, None, "reference"),  # decode's one-token queries
    ("tpu", 1280, 96, None, "reference"),  # a head_dim of part of a lane tile
    ("tpu", 1280, 64, None, "flash"),    # 64 wide: spans the flat operand
    ("tpu", 1280, 128, 256, "flash"),    # a static window: the kernel's mask
    ("tpu", 1280, 128, jnp.int32(256), "reference"),  # a traced one is not
])
def test_one_chip_lowering(monkeypatch, backend, q_len, head_dim, window,
                           want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    sp = SPConfig(strategy="full", sp_axes=("model",))
    assert attention_lowering(sp, _mesh(1, 1), q_len, head_dim,
                              window) == want


@pytest.mark.parametrize("causal", [False, True])
def test_one_chip_flash_differentiates_as_the_oracle(monkeypatch, mesh1,
                                                     causal):
    """Where SP=1 runs the kernel, the forward is the kernel's and the
    gradient the oracle's (the Pallas call has no transpose rule), so a
    train step differentiates through it.  The platform test is reported
    as a TPU's; the kernel itself still runs interpreted on the CPU."""
    monkeypatch.setattr(strategy, "pallas_interpret", lambda: False)
    sp = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
    q, k, v = _mk(jax.random.PRNGKey(8), 1, 64, 64, 2, 2, 128, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    mask = MaskSpec(causal=causal)
    flash = lambda q, k, v: sp_attention(q, k, v, mesh=mesh1, cfg=sp,
                                         causal=causal)
    oracle = lambda q, k, v: reference_attention(q, k, v, mask=mask)
    assert "pallas_call" in str(jax.make_jaxpr(flash)(q, k, v))
    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v),
                               rtol=2e-5, atol=2e-5)
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) * w)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)


def test_attention_lowering_names_the_sp_strategy(monkeypatch):
    """The kernel on a one-device mesh; the oracle at SP=1 on several
    (GSPMD does not partition a Pallas call); at SP > 1 the strategy and
    the attend inside its stages: the kernel where the predicate holds,
    the materialised oracle where it does not (the CPU, a head_dim the
    kernel does not take), the fused ring kernel under the Pallas comm
    backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sp = SPConfig(strategy="swift_torus", sp_axes=("model",))
    assert attention_lowering(sp, _mesh(1, 1), 1280, 128) == "flash"
    assert attention_lowering(sp, _mesh(2, 1), 1280, 128) == "reference"
    assert attention_lowering(sp, _mesh(1, 4), 1280, 128) == (
        "swift_torus+flash")
    assert attention_lowering(sp, _mesh(1, 4), 17776, 64) == (
        "swift_torus+flash")
    assert attention_lowering(sp, _mesh(1, 4), 1280, 96) == (
        "swift_torus+reference")
    pallas = dataclasses.replace(sp, strategy="ring", comm_backend="pallas")
    assert attention_lowering(pallas, _mesh(1, 4), 1280, 128) == (
        "ring+ring_flash")
    full = dataclasses.replace(sp, strategy="full")
    assert attention_lowering(full, _mesh(1, 4), 1280, 128) == "reference"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert attention_lowering(sp, _mesh(1, 4), 1280, 128) == (
        "swift_torus+reference")
