"""Pallas-backend ring/torus attention vs the single-device reference on
the 8-device CPU mesh (interpret mode) — the acceptance gate for the
fused comm path (DESIGN.md §8.1).

Covers the carried (O', l, m) merge across ring steps (P_r > 1 circulates
the kernel state), GQA head grouping, causal/window masks, both torus
strategies (swift_torus per-stage RINGATTN and the usp-like monolithic
gather), and xla-vs-pallas parity of the full sp_attention outputs.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.compat import make_mesh
from repro.core import MaskSpec, SPConfig, reference_attention, sp_attention
from repro.core.collectives import GroupLayout
from repro.core.ring import ring_attention
from repro.core.softmax import attend_partial, finalize

TOL = dict(rtol=1e-5, atol=1e-5)


def _mk(seed, b, l, hq, hkv, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, l, hq, d), dtype),
            jax.random.normal(ks[1], (b, l, hkv, d), dtype),
            jax.random.normal(ks[2], (b, l, hkv, d), dtype))


def _ring_mesh():
    return make_mesh((4, 2), ("sp", "data"))


def _run_ring(mesh, layout, q, k, v, *, backend, causal=False, window=None,
              extra_chunk=None):
    """ring_attention under shard_map; optionally merge an accum partial
    computed from an extra resident KV chunk (the carried-state path)."""
    ls = q.shape[1] // 4

    def body(q, k, v, ek=None, ev=None):
        qs = q.shape[1]
        qp = layout.seq_offset_of_rank(qs) + jnp.arange(qs)
        kpfn = lambda r: r * ls + jnp.arange(ls)
        accum = None
        if ek is not None:
            e_off = extra_chunk[2]
            accum = attend_partial(
                q, ek, ev,
                mask=MaskSpec(causal=causal, window=window, q_pos=qp,
                              k_pos=e_off + jnp.arange(ek.shape[1])))
        part = ring_attention(
            q, k, v, layout, q_pos=qp, k_pos_fn=kpfn, causal=causal,
            window=window, accum=accum, backend=backend)
        return finalize(part, dtype=q.dtype)

    spec = P(("data",), ("sp",), None, None)
    espec = P(("data",), None, None, None)
    if extra_chunk is not None:
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec, spec, spec, espec, espec), out_specs=spec,
            check_vma=False)
        return jax.jit(fn)(q, k, v, extra_chunk[0], extra_chunk[1])
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                       check_vma=False)
    return jax.jit(fn)(q, k, v)


@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 24)])
def test_ring_pallas_matches_reference(causal, window):
    mesh = _ring_mesh()
    layout = GroupLayout(("sp",), 1, 4, ulysses_outer=True)
    q, k, v = _mk(0, 2, 64, 2, 2, 16)
    out = _run_ring(mesh, layout, q, k, v, backend="pallas", causal=causal,
                    window=window)
    ref = reference_attention(q, k, v,
                              mask=MaskSpec(causal=causal, window=window))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_ring_pallas_gqa_grouping():
    """GQA: 4 q heads share 2 kv heads through the kernel's index_map."""
    mesh = _ring_mesh()
    layout = GroupLayout(("sp",), 1, 4, ulysses_outer=True)
    q, k, v = _mk(1, 2, 64, 4, 2, 16)
    out = _run_ring(mesh, layout, q, k, v, backend="pallas", causal=True)
    ref = reference_attention(q, k, v, mask=MaskSpec(causal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_ring_pallas_carried_state_merge():
    """An accum Partial (extra KV chunk attended before the ring) must
    merge exactly with the kernel-carried (O', l, m) ring state."""
    mesh = _ring_mesh()
    layout = GroupLayout(("sp",), 1, 4, ulysses_outer=True)
    q, k, v = _mk(2, 2, 64, 2, 2, 16)
    eks = jax.random.split(jax.random.PRNGKey(9), 2)
    ek = jax.random.normal(eks[0], (2, 32, 2, 16))
    ev = jax.random.normal(eks[1], (2, 32, 2, 16))
    out = _run_ring(mesh, layout, q, k, v, backend="pallas", causal=True,
                    extra_chunk=(ek, ev, 64))
    # reference: attention over [k; ek] with ek positioned after the ring KV
    kk = jnp.concatenate([k, ek], axis=1)
    vv = jnp.concatenate([v, ev], axis=1)
    ref = reference_attention(q, kk, vv, mask=MaskSpec(causal=True))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_ring_backend_parity():
    mesh = _ring_mesh()
    layout = GroupLayout(("sp",), 1, 4, ulysses_outer=True)
    q, k, v = _mk(3, 2, 64, 2, 2, 16)
    a = _run_ring(mesh, layout, q, k, v, backend="xla", causal=True)
    b = _run_ring(mesh, layout, q, k, v, backend="pallas", causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


# ---------------------------------------------------------------------------
# full sp_attention strategies with the pallas backend (mesh8: pod 2 x
# data 2 x model 2 -> P_u = P_r = 2 over the flattened SP axes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["swift_torus", "swift", "usp"])
@pytest.mark.parametrize("causal", [False, True])
def test_sp_attention_pallas_matches_reference(mesh8, strategy, causal):
    sp = SPConfig(strategy=strategy, sp_axes=("pod", "model"),
                  batch_axes=("data",), comm_backend="pallas")
    q, k, v = _mk(4, 2, 32, 2, 2, 16)
    out = jax.jit(
        lambda q, k, v: sp_attention(q, k, v, mesh=mesh8, cfg=sp,
                                     causal=causal))(q, k, v)
    ref = reference_attention(q, k, v, mask=MaskSpec(causal=causal))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_sp_attention_gqa_pallas(mesh8):
    sp = SPConfig(strategy="swift_torus", sp_axes=("pod", "model"),
                  batch_axes=("data",), comm_backend="pallas")
    q, k, v = _mk(5, 2, 32, 4, 2, 16)
    out = jax.jit(
        lambda q, k, v: sp_attention(q, k, v, mesh=mesh8, cfg=sp))(q, k, v)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_sp_attention_backend_parity_and_schedule(mesh8):
    base = SPConfig(strategy="swift_torus", sp_axes=("pod", "model"),
                    batch_axes=("data",))
    q, k, v = _mk(6, 2, 32, 2, 2, 16)
    outs = {}
    for backend in ("xla", "pallas"):
        cfg = dataclasses.replace(base, comm_backend=backend)
        with comm.record(backend) as tr:
            outs[backend] = jax.jit(
                lambda q, k, v, c=cfg: sp_attention(q, k, v, mesh=mesh8,
                                                    cfg=c))(q, k, v)
        if backend == "pallas":
            rep = comm.validate_semaphores(tr)
            assert rep.ok, rep.summary()
            assert rep.puts > 0
            assert all(e.backend == "pallas" for e in tr.events)
    np.testing.assert_allclose(np.asarray(outs["xla"]),
                               np.asarray(outs["pallas"]), **TOL)
