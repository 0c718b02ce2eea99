"""Backend parity: the same transfer programs through xla-Channels vs
pallas-Channels (interpret mode) must move identical bytes.

The Pallas backend's emulation branch (DESIGN.md §8.1) keeps the wire
move a ppermute and adds the semaphore-tracked landing kernel, so parity
is *bitwise* for pure transfers — any discrepancy is a delivery bug, not
numerics.  Tests parameterize over dtypes (fp32/bf16) and uneven shard
sizes (shapes far from any tile multiple).

Device-count note: this file runs in the outer suite (1 device under the
plain pytest invocation; 8 fake devices in CI).  Multi-hop routes only
exist with >= 8 devices, so those cases skip on single-device runs; the
always-on multidevice coverage lives in tests/multidevice/test_ring_pallas.py.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.comm import pallas_backend
from repro.compat import make_mesh
from repro.core.collectives import GroupLayout

N_DEV = jax.device_count()
needs8 = pytest.mark.skipif(N_DEV < 8, reason="needs 8 (fake) devices")

DTYPES = [jnp.float32, jnp.bfloat16]
UNEVEN_SHAPES = [(3, 5), (7, 3, 2), (1, 13)]  # per-shard, no tile alignment


def _mesh_sp():
    return make_mesh((N_DEV,), ("sp",))


def _sharded(key, shape, dtype):
    """Global array whose leading dim shards over the full sp axis."""
    x = jax.random.normal(key, (N_DEV, *shape), jnp.float32)
    return x.astype(dtype)


def _run_program(mesh, fn, *xs):
    spec = P("sp")
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * len(xs), out_specs=spec,
        check_vma=False))(*xs)


# ---------------------------------------------------------------------------
# landing kernel: the interpret-mode delivery path preserves values exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", UNEVEN_SHAPES)
def test_landing_copy_bitwise(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    ox, oy = pallas_backend.landing_copy((x, y))
    assert ox.dtype == dtype and oy.dtype == dtype
    np.testing.assert_array_equal(np.asarray(ox), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(oy), np.asarray(y))


# ---------------------------------------------------------------------------
# ring shift parity (any device count: the size-N_DEV rotation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", UNEVEN_SHAPES)
def test_ring_shift_parity(dtype, shape):
    mesh = _mesh_sp()
    layout = GroupLayout(("sp",), 1, N_DEV, ulysses_outer=True)
    x = _sharded(jax.random.PRNGKey(2), shape, dtype)

    outs = {}
    for backend in ("xla", "pallas"):
        def body(xs, b=backend):
            return comm.ring_shift(layout, xs, backend=b,
                                   interpret=True).wait()
        outs[backend] = _run_program(mesh, body, x)
    np.testing.assert_array_equal(np.asarray(outs["xla"]),
                                  np.asarray(outs["pallas"]))


def test_ring_shift_pallas_records_semaphores():
    mesh = _mesh_sp()
    layout = GroupLayout(("sp",), 1, N_DEV, ulysses_outer=True)
    x = _sharded(jax.random.PRNGKey(3), (2, 3), jnp.float32)

    def body(xs):
        return comm.ring_shift(layout, xs, backend="pallas",
                               interpret=True).wait()

    with comm.record("shift") as tr:
        _run_program(mesh, body, x)
    assert len(tr.events) == 1 and tr.events[0].backend == "pallas"
    kinds = [e.kind for e in tr.sem_events]
    assert kinds == ["put", "signal", "wait"]
    assert comm.validate_semaphores(tr).ok


# ---------------------------------------------------------------------------
# distance-k torus hop + staged a2a parity (needs a real (P_u, P_r) torus)
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("k", [1, 2, 3])
def test_torus_hop_parity(dtype, k):
    mesh = _mesh_sp()
    layout = GroupLayout(("sp",), 4, 2, ulysses_outer=True)
    x = _sharded(jax.random.PRNGKey(4), (3, 5), dtype)

    outs = {}
    for backend in ("xla", "pallas"):
        def body(xs, b=backend):
            return comm.torus_hop(layout, k, xs, backend=b,
                                  interpret=True).wait()
        outs[backend] = _run_program(mesh, body, x)
    np.testing.assert_array_equal(np.asarray(outs["xla"]),
                                  np.asarray(outs["pallas"]))


@needs8
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("feat", [5, 13])  # uneven non-split dims
def test_staged_a2a_parity(dtype, feat):
    mesh = _mesh_sp()
    layout = GroupLayout(("sp",), 4, 2, ulysses_outer=True)
    # split axis (per-shard axis 1) must divide by P_u = 4; others uneven
    x = _sharded(jax.random.PRNGKey(5), (4, feat), dtype)

    outs = {}
    for backend in ("xla", "pallas"):
        def body(xs, b=backend):
            return comm.staged_all_to_all(xs, layout, split_axis=1,
                                          backend=b, interpret=True)
        outs[backend] = _run_program(mesh, body, x)
    np.testing.assert_array_equal(np.asarray(outs["xla"]),
                                  np.asarray(outs["pallas"]))


@needs8
@pytest.mark.parametrize("dtype", DTYPES)
def test_staged_ungroup_parity(dtype):
    mesh = _mesh_sp()
    layout = GroupLayout(("sp",), 4, 2, ulysses_outer=True)
    x = _sharded(jax.random.PRNGKey(6), (8, 3), dtype)

    outs = {}
    for backend in ("xla", "pallas"):
        def body(xs, b=backend):
            stacked = comm.staged_all_to_all(xs, layout, split_axis=1,
                                             backend=b, interpret=True)
            return comm.staged_ungroup(stacked, layout, concat_axis=1,
                                       backend=b, interpret=True)
        outs[backend] = _run_program(mesh, body, x)
    # a2a followed by its inverse is the identity — on both backends
    np.testing.assert_array_equal(np.asarray(outs["xla"]),
                                  np.asarray(outs["pallas"]))
    np.testing.assert_array_equal(np.asarray(outs["pallas"]),
                                  np.asarray(x))


# ---------------------------------------------------------------------------
# semaphore pairing of randomly generated Stream programs (hypothesis)
# ---------------------------------------------------------------------------

from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(0, 10**6), st.booleans())
def test_random_stream_program_semaphores_pair(n_stages, seed, defer_waits):
    """Any program of pallas-channel puts (waits in any order AFTER their
    put) records a valid semaphore pairing."""
    rng = random.Random(seed)
    layout = GroupLayout(("sp",), 1, N_DEV, ulysses_outer=True)
    mesh = _mesh_sp()

    def body(xs):
        stream = comm.Stream(f"rand{seed}", backend="pallas", interpret=True)
        futs, out = [], xs
        for _ in range(n_stages):
            futs.append(comm.ring_shift(
                layout, out, shift=rng.choice([1, N_DEV - 1] if N_DEV > 1
                                              else [1]),
                stream=stream))
            if not defer_waits:
                out = futs[-1].wait()
        if defer_waits:
            for f in futs:
                out = f.wait()
        return out

    with comm.record("rand") as tr:
        _run_program(mesh, body, _sharded(jax.random.PRNGKey(7), (2, 2),
                                          jnp.float32))
    assert len(tr.events) == n_stages
    rep = comm.validate_semaphores(tr)
    assert rep.ok, rep.summary()
    assert rep.puts == n_stages and rep.waits == n_stages


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10**6), st.sampled_from(["wait_first", "double_signal",
                                               "orphan_signal", "no_signal"]))
def test_malformed_semaphore_schedules_flagged(seed, defect):
    """Hand-built broken schedules must fail validation (the property the
    gate relies on: a buggy fused kernel wrapper cannot pass silently)."""
    from repro.comm.trace import ScheduleTrace, SemEvent

    tr = ScheduleTrace("broken")
    sem = f"chan.s0#{seed}"
    if defect == "wait_first":
        tr.sem_events = [SemEvent("wait", sem), SemEvent("put", sem),
                         SemEvent("signal", sem)]
    elif defect == "double_signal":
        tr.sem_events = [SemEvent("put", sem), SemEvent("signal", sem),
                         SemEvent("signal", sem), SemEvent("wait", sem)]
    elif defect == "orphan_signal":
        tr.sem_events = [SemEvent("signal", sem)]
    else:  # no_signal
        tr.sem_events = [SemEvent("put", sem), SemEvent("wait", sem)]
    assert not comm.validate_semaphores(tr).ok


def test_blocking_wait_flagged():
    """An overlap-intent put whose wait has no compute between is the
    schedule bug the fused kernel exists to avoid — must be flagged."""
    from repro.comm.trace import ScheduleTrace, SemEvent

    tr = ScheduleTrace("blocking")
    tr.sem_events = [
        SemEvent("put", "a", overlap=True), SemEvent("signal", "a"),
        SemEvent("wait", "a"), SemEvent("compute", ""),
    ]
    rep = comm.validate_semaphores(tr)
    assert not rep.ok and "blocking wait" in rep.failures[0]

    good = ScheduleTrace("overlapped")
    good.sem_events = [
        SemEvent("put", "a", overlap=True), SemEvent("signal", "a"),
        SemEvent("compute", ""), SemEvent("wait", "a"),
    ]
    assert comm.validate_semaphores(good).ok
