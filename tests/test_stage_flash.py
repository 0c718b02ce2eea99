"""The SP stage attend's flash lowering (``core.ring.FlashLowering`` and
``_flash_attend``) against ``attend_partial``, the oracle it replaces at
SP > 1: interpreted on the CPU, small shapes.

Every case runs one stage attend as a torus or ring stage does: q and a KV
chunk moved into the kernel's layout, masks by global positions of
discontiguous chunks, and the running (O', l, m) fresh or carried in; or
the state written at a chunk's rows of a larger accumulator (the torus's
gathered-Q rows), or finalized.  The returned state is checked field by
field, in bf16 tolerance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ring import ORACLE, FlashLowering
from repro.core.softmax import (MaskSpec, Partial, attend_partial, finalize,
                                merge)
from repro.kernels.ops import SCORE_ELEMS, block_sizes

B = 1


def _qkv(seed, length, hq, hkv, d):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, length, hq, d), jnp.bfloat16)
    kv = [jax.random.normal(k_, (B, length, hkv, d), jnp.bfloat16)
          for k_ in ks[1:]]
    return q, kv


def _natural(st: Partial, length: int, chunks: int = 1) -> Partial:
    """The kernel layout's state back as ``attend_partial`` lays it out:
    ``chunks`` chunks of ``length`` rows, each padded to whole blocks."""
    bh, lq, d = st.o.shape
    h = bh // B
    o = st.o.reshape(B, h, chunks, lq // chunks, d)[:, :, :, :length]
    o = o.reshape(B, h, -1, d).transpose(0, 2, 1, 3)
    lm = lambda x: x[..., 0].reshape(B, h, chunks, -1)[..., :length].reshape(
        B, h, -1)
    return Partial(o, lm(st.l), lm(st.m))


def _chunk(p: Partial, j: int, length: int) -> Partial:
    """Chunk ``j``'s rows of a state in ``attend_partial``'s layout."""
    rows = slice(j * length, (j + 1) * length)
    return Partial(p.o[:, rows], p.l[..., rows], p.m[..., rows])


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
    run = lambda k_, v_, kp, st: low.attend(
        low.q(q), low.kv(k_), low.kv(v_), low.q_pos(q_pos), kp, st,
        scale=None, causal=causal, window=window)
    state = run(k0, v0, k0_pos, None) if carried else None
    _check(_natural(run(k, v, k_pos, state), length), want)


@pytest.mark.parametrize("blocks", [None, (16, 16)],
                         ids=["derived", "k_padded"])
def test_stage_attend_differentiates_as_the_oracle(blocks):
    """The kernel's gradient is the oracle's, through the carried state
    too: two chained stage attends, the second finalizing in the kernel,
    against attend_partial's; with K padded, through the pad keys' mask
    as well."""
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
        for k_, v_, last in ((k0, v0, False), (k, v, True)):
            st = low.attend(low.q(q), low.kv(k_), low.kv(v_),
                            low.q_pos(pos), pos, st, scale=None,
                            causal=True, window=None, finalize=last)
        return low.finalize(st)

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


CHUNKS, SRC = 3, 1  # a gathered-Q accumulator of three chunks; q's is 1


def _placed_case(d, causal, dtype):
    """q chunk ``SRC`` of a three-chunk accumulator against one KV chunk:
    44 rows, padded to a whole block in the kernel's layout; GQA."""
    length, hkv = 44, 2
    q, (k, v, *_) = _qkv(d + causal, length, 2 * hkv, hkv, d)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    q_pos = SRC * length + jnp.arange(length)
    k_pos = length // 2 + jnp.arange(length)
    low = FlashLowering.of(B, length, length, d, dtype)
    rows = low.q(q).shape[1]
    # a filled accumulator in the kernel's layout: l > 0, m finite
    bh, n = q.shape[2] * B, CHUNKS * rows
    r = jax.random.split(jax.random.PRNGKey(d), 3)
    acc = Partial(jax.random.normal(r[0], (bh, n, d)),
                  jax.random.uniform(r[1], (bh, n, 1), minval=1.0,
                                     maxval=4.0),
                  jax.random.normal(r[2], (bh, n, 1)))
    return low, rows, length, q, k, v, q_pos, k_pos, acc


def _placed(write, low, rows, length, q, k, v, q_pos, k_pos, acc, causal):
    """Both lowerings' attend of the case, written into the accumulator
    (``created``: one the call makes; ``aliased``: ``acc``), each in
    ``attend_partial``'s layout: the kernel's, and ``_put_slice``'s."""
    n_nat = (B, CHUNKS * length, q.shape[2], q.shape[3])
    if write == "created":
        dest, base = low.empty(acc.o.shape), ORACLE.empty(n_nat)
    else:
        dest, base = acc, _natural(acc, length, CHUNKS)
    got = low.attend(low.q(q), low.kv(k), low.kv(v), low.q_pos(q_pos), k_pos,
                     None, scale=None, causal=causal, window=None,
                     into=(dest, SRC * rows))
    want = ORACLE.attend(q, k, v, q_pos, k_pos, None, scale=None,
                         causal=causal, window=None,
                         into=(base, SRC * length))
    return _natural(got, length, CHUNKS), want, got


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [False, True], ids=["open", "causal"])
@pytest.mark.parametrize("write", ["created", "aliased"])
def test_stage_attend_writes_its_rows_in_place(d, causal, write):
    """A stage attend written at a block offset of a larger accumulator,
    as the torus's stage 0 (``created``) and Pull-Q stages (``aliased``)
    do: q's rows match ``attend_partial`` placed by ``_put_slice``; an
    aliased accumulator's other rows pass through bit for bit."""
    case = _placed_case(d, causal, jnp.bfloat16)
    low, rows, length, *_, acc = case
    got, want, raw = _placed(write, *case, causal)
    _check(_chunk(got, SRC, length), _chunk(want, SRC, length))
    if write == "aliased":
        other = np.r_[0:SRC * rows, (SRC + 1) * rows:CHUNKS * rows]
        for g, a in zip(raw, acc):
            np.testing.assert_array_equal(g[:, other], a[:, other])


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [False, True], ids=["open", "causal"])
def test_last_stage_attend_finalizes_in_the_kernel(d, causal):
    """The chain's last attend, as the torus's last Pull-KV stage: the
    state carried in, O = O' / l out in q's dtype, against the oracle's
    attend, merge and ``finalize``."""
    length = 44
    q, (k0, v0, k, v) = _qkv(d, length, 4, 2, d)
    q_pos = 2 * length + jnp.arange(length)
    kp0, kp = jnp.arange(length), length // 2 + jnp.arange(length)
    low = FlashLowering.of(B, length, length, d, q.dtype)
    run = lambda k_, v_, kp_, st, last: low.attend(
        low.q(q), low.kv(k_), low.kv(v_), low.q_pos(q_pos), kp_, st,
        scale=None, causal=causal, window=None, finalize=last)
    o = low.finalize(run(k, v, kp, run(k0, v0, kp0, None, False), True))
    spec = lambda p: MaskSpec(causal=causal, q_pos=q_pos, k_pos=p)
    want = finalize(merge(attend_partial(q, k0, v0, mask=spec(kp0)),
                          attend_partial(q, k, v, mask=spec(kp))),
                    dtype=q.dtype)
    assert o.dtype == q.dtype and o.shape == q.shape
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("write", ["created", "aliased"])
def test_placed_stage_attend_differentiates_as_the_oracle(write):
    """The gradient of an attend written into the accumulator is the
    oracle's: ``attend_partial``, then ``_put_slice``'s VJP (an aliased
    accumulator's gradient is the cotangent off q's rows)."""
    case = _placed_case(64, True, jnp.float32)
    low, rows, length, q, k, v, q_pos, k_pos, acc = case
    w = jax.random.split(jax.random.PRNGKey(7), 2)

    def loss(which, q, k, v, acc):
        got, want, _ = _placed(write, low, rows, length, q, k, v, q_pos,
                               k_pos, acc, True)
        p = got if which == "flash" else want
        if write == "created":  # the other rows are never written
            p = _chunk(p, SRC, length)
        return (jnp.sum(p.o * jax.random.normal(w[0], p.o.shape))
                + jnp.sum(p.l * jax.random.normal(w[1], p.l.shape)))

    args = (q, k, v, acc)
    argnums = (0, 1, 2) if write == "created" else (0, 1, 2, 3)
    got = jax.grad(lambda *a: loss("flash", *a), argnums=argnums)(*args)
    want = jax.grad(lambda *a: loss("oracle", *a), argnums=argnums)(*args)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
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
