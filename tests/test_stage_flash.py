"""The SP stage attend's flash lowering (``core.ring.FlashLowering`` and
``_flash_attend``) against ``attend_partial``, the oracle it replaces at
SP > 1: interpreted on the CPU, small shapes.

Every case runs one stage attend as a torus or ring stage does: q and a KV
chunk moved into the kernel's layout, masks by global positions of
discontiguous chunks, and the running (O', l, m) fresh or carried in.
The returned state is checked field by field, in bf16 tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ring import FlashLowering, _flash_attend
from repro.core.softmax import MaskSpec, Partial, attend_partial, merge
from repro.kernels.ops import SCORE_ELEMS, block_sizes

B = 1


def _qkv(seed, length, hq, hkv, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, length, hq, d), jnp.bfloat16)
    kv = [jax.random.normal(k_, (B, length, hkv, d), jnp.bfloat16)
          for k_ in ks[1:]]
    return q, kv


def _natural(st: Partial, length: int) -> Partial:
    """The kernel layout's state back as ``attend_partial`` lays it out."""
    bh, _, d = st.o.shape
    h = bh // B
    o = st.o.reshape(B, h, -1, d)[:, :, :length].transpose(0, 2, 1, 3)
    lm = lambda x: x[..., 0].reshape(B, h, -1)[:, :, :length]
    return Partial(o, lm(st.l), lm(st.m))


def _check(got: Partial, want: Partial):
    l_ok = want.l > 0
    np.testing.assert_array_equal(got.l > 0, l_ok)  # the same rows masked
    norm = lambda p: np.where(np.swapaxes(l_ok, 1, 2)[..., None],
                              p.o / np.swapaxes(np.where(l_ok, p.l, 1.0),
                                                1, 2)[..., None], 0.0)
    np.testing.assert_allclose(norm(got), norm(want), rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got.l, want.l, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.where(l_ok, got.m, 0.0),
                               np.where(l_ok, want.m, 0.0),
                               rtol=2e-2, atol=5e-2)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("length,blocks", [
    (32, None),       # whole blocks from the shape: nothing padded
    (44, None),       # q padded to a whole block, K one unpadded block
    (44, (16, 16)),   # K padded as well: its pad keys masked by position
])
@pytest.mark.parametrize("mask", ["none", "causal", "window"])
@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("carried", [False, True])
def test_stage_attend_matches_attend_partial(d, length, blocks, mask, group,
                                             carried):
    hkv = 2
    hq = hkv * group
    q, (k0, v0, k, v) = _qkv(d + length + group, length, hq, hkv, d)
    causal, window = mask == "causal", (9 if mask == "window" else None)
    # discontiguous global positions, as the torus's gathered chunks have:
    # the query chunk sits after the carried chunk and overlaps this one
    q_pos = 2 * length + jnp.arange(length)
    k0_pos = jnp.arange(length)
    k_pos = length + length // 2 + jnp.arange(length)
    spec = lambda kp: MaskSpec(causal=causal, window=window, q_pos=q_pos,
                               k_pos=kp)
    want = attend_partial(q, k, v, mask=spec(k_pos))
    if carried:
        first = attend_partial(q, k0, v0, mask=spec(k0_pos))
        want = merge(first, want)

    low = FlashLowering.of(B, length, length, d, q.dtype)
    if blocks is not None:
        low = low._replace(block_q=blocks[0], block_k=blocks[1])
    masked = (causal or window is not None
              or low.k_len % low.block_k != 0)
    run = lambda k_, v_, kp, st: _flash_attend(
        low.q(q), low.kv(k_), low.kv(v_), low.q_pos(q_pos), low.k_pos(kp),
        st, None, causal, window, low.block_q, low.block_k, masked)
    state = run(k0, v0, k0_pos, None) if carried else None
    _check(_natural(run(k, v, k_pos, state), length), want)


@pytest.mark.parametrize("blocks", [None, (16, 16)],
                         ids=["derived", "k_padded"])
def test_stage_attend_differentiates_as_the_oracle(blocks):
    """The kernel's gradient is the oracle's, through the carried state
    too: two chained stage attends, finalized, against attend_partial's;
    with K padded, through the pad keys' mask as well."""
    d, length = 64, 44
    q, (k0, v0, k, v) = _qkv(3, length, 4, 2, d)
    q, k0, v0, k, v = (x.astype(jnp.float32) for x in (q, k0, v0, k, v))
    pos = jnp.arange(length)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)
    low = FlashLowering.of(B, length, length, d, q.dtype)
    if blocks is not None:
        low = low._replace(block_q=blocks[0], block_k=blocks[1])

    def flash(q, k0, v0, k, v):
        st = None
        for k_, v_ in ((k0, v0), (k, v)):
            st = _flash_attend(low.q(q), low.kv(k_), low.kv(v_),
                               low.q_pos(pos), low.k_pos(pos), st, None,
                               True, None, low.block_q, low.block_k, True)
        return low.finalize(st, q.dtype)

    def oracle(q, k0, v0, k, v):
        m = MaskSpec(causal=True)
        p = merge(attend_partial(q, k0, v0, mask=m),
                  attend_partial(q, k, v, mask=m))
        o = p.o / jnp.swapaxes(p.l, 1, 2)[..., None]
        return o.astype(q.dtype)

    loss = lambda f: lambda *a: jnp.sum(f(*a) * w)
    args = (q, k0, v0, k, v)
    np.testing.assert_allclose(flash(*args), oracle(*args), rtol=1e-4,
                               atol=1e-4)
    got = jax.grad(loss(flash), argnums=range(5))(*args)
    want = jax.grad(loss(oracle), argnums=range(5))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q_len,k_len,want", [
    (4444, 4444, (448, 4444)),    # a torus chunk of the video cell
    (4 * 4480, 4444, (448, 4444)),  # its gathered Q, chunk-padded
    (4352, 4352, (272, 4352)),    # flux_img_mix at SP=1 (head_dim 128)
])
def test_stage_blocks_from_shape(q_len, k_len, want):
    """K is one unpadded block spanning the chunk; q pads to the fewest
    blocks within the score budget.  The one-chip image blocks stay."""
    d = 128 if q_len == 4352 else 64
    bq, bk = block_sizes(q_len, k_len, d, 2)
    assert (bq, bk) == want
    assert bq * bk <= SCORE_ELEMS and bq % 16 == 0
