"""repro.comm channels/streams on the 8-fake-device mesh: equivalence of
the Stream-based transfer programs against the raw lax collectives they
replaced, and trace-vs-compiled-HLO overlap validation (the ROADMAP
bubble-term check for the displaced pipeline)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import comm
from repro.configs import get_reduced
from repro.core import SPConfig, sp_attention
from repro.core.collectives import (
    GroupLayout,
    grouped_all_to_all,
    monolithic_all_to_all,
    ungroup_all_to_all,
)
from repro.core.pipefusion import PipelineConfig
from repro.launch.mesh import make_hybrid_mesh
from repro.models import ParallelContext, get_model
from repro.models.dit import dit_forward_displaced
from repro.serving import SamplerConfig
from repro.serving.sampler import hybrid_state_shape

SP_AXES = ("pod", "model")


def _layout(p_u, p_r):
    return GroupLayout(SP_AXES, p_u, p_r, ulysses_outer=True)


def _smap(fn, mesh, spec):
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)


# ---------------------------------------------------------------------------
# equivalence vs raw lax collectives
# ---------------------------------------------------------------------------

def test_stream_ring_shift_matches_lax_ppermute(mesh8, rng):
    layout = _layout(2, 2)
    x = jax.random.normal(rng, (8, 16))
    spec = P(SP_AXES)
    via_comm = _smap(lambda xs: comm.ring_shift(layout, xs).wait(),
                     mesh8, spec)
    via_lax = _smap(
        lambda xs: lax.ppermute(xs, SP_AXES, perm=layout.ring_perm(1)),
        mesh8, spec)
    np.testing.assert_array_equal(np.asarray(via_comm(x)),
                                  np.asarray(via_lax(x)))


def test_staged_all_to_all_matches_monolithic(mesh8, rng):
    """Full-axis Ulysses group: the staged channel program must deliver
    exactly what the atomic lax.all_to_all delivers."""
    layout = _layout(4, 1)
    x = jax.random.normal(rng, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)

    def staged(xs):
        return comm.staged_all_to_all(xs, layout, split_axis=2)

    def monolithic(xs):
        return monolithic_all_to_all(xs, layout, split_axis=2)

    out_spec = P(None, None, SP_AXES, None, None)
    f1 = jax.shard_map(staged, mesh=mesh8, in_specs=(spec,), out_specs=out_spec,
                       check_vma=False)
    f2 = jax.shard_map(monolithic, mesh=mesh8, in_specs=(spec,),
                       out_specs=out_spec, check_vma=False)
    np.testing.assert_array_equal(np.asarray(f1(x)), np.asarray(f2(x)))


@pytest.mark.parametrize("p_u,p_r", [(2, 2), (4, 1)])
def test_grouped_ungroup_roundtrip(p_u, p_r, mesh8, rng):
    layout = _layout(p_u, p_r)
    x = jax.random.normal(rng, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)

    def roundtrip(xs):
        stacked = grouped_all_to_all(xs, layout, split_axis=2)
        return ungroup_all_to_all(stacked, layout, concat_axis=2)

    f = _smap(roundtrip, mesh8, spec)
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x), rtol=0, atol=0)


def test_pipe_handoff_value_preserving_and_traced(rng):
    mesh = make_hybrid_mesh(cfg=1, pipe=2, data=2, model=2)
    x = jax.random.normal(rng, (4, 8, 16))

    def f(xs):
        return comm.pipe_handoff(xs, mesh, "pipe", batch_axes=("data",))

    with comm.record("pipe") as tr:
        lowered = jax.jit(f).lower(x)
    assert len(tr.events) == 1
    (e,) = tr.events
    assert e.axes == ("pipe",) and e.overlaps == "stage compute"
    # replicated over the pipe axis, the rotation is value-preserving
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)), np.asarray(x))
    # ... but it is a *real* wire transfer in the compiled program
    report = comm.validate(tr, lowered.compile().as_text(), mesh,
                           require_overlap=False)
    assert report.hlo_permutes >= 1
    assert not any("no collective-permute" in f_ for f_ in report.failures)


# ---------------------------------------------------------------------------
# trace-vs-HLO overlap validation
# ---------------------------------------------------------------------------

def test_torus_schedule_validates_against_hlo(mesh8, rng):
    """Every put of the Torus schedule must appear as a collective-permute
    with the intended route, and each overlap intent must be admissible in
    the compiled program."""
    kq, kk, kv = jax.random.split(rng, 3)
    # 2 heads on the 4-way SP group => P_u = gcd(4, 2) = 2, P_r = 2: both
    # the torus hops AND the intra-ring rotations appear in the schedule
    q = jax.random.normal(kq, (2, 32, 2, 16))
    k = jax.random.normal(kk, (2, 32, 2, 16))
    v = jax.random.normal(kv, (2, 32, 2, 16))
    cfg = SPConfig(strategy="swift_torus", sp_axes=SP_AXES,
                   batch_axes=("data",))

    def fn(q, k, v):
        return sp_attention(q, k, v, mesh=mesh8, cfg=cfg)

    with comm.record("torus") as tr:
        lowered = jax.jit(fn).lower(q, k, v)
    assert tr.events, "no channel puts recorded for the torus schedule"
    assert any(e.stream == "torus" for e in tr.events)
    assert any(e.stream == "ring" for e in tr.events)
    report = comm.validate(tr, lowered.compile().as_text(), mesh8)
    assert report.ok, report.summary()
    assert report.overlapped, "no overlap intent validated"


def test_displaced_pipe_handoff_overlaps_stage_compute(rng):
    """The ROADMAP bubble-term validation: the displaced pipeline's stage
    hand-off must be an explicit collective-permute over the pipe axis
    that the compiled HLO can overlap with stage compute (patch p+1's
    transfer vs patch p's compute)."""
    mesh = make_hybrid_mesh(cfg=1, pipe=2, data=1, model=4)
    cfg = dataclasses.replace(get_reduced("flux-12b"), dtype="float32",
                              n_heads=4, n_kv_heads=4)
    bundle = get_model(cfg)
    params, _ = bundle.init(cfg, jax.random.PRNGKey(0), 1)
    sp = SPConfig(strategy="swift_torus", sp_axes=("model",),
                  batch_axes=("data",), pp_axis="pipe")
    ctx = ParallelContext(mesh, sp, "prefill")
    sc = SamplerConfig(num_steps=2,
                       pipeline=PipelineConfig(pp=2, warmup_steps=1))
    seq = 32
    lat = jax.random.normal(rng, (1, seq, 64), jnp.float32)
    cond = jax.random.normal(jax.random.PRNGKey(1),
                             (1, cfg.text_tokens, cfg.cond_width), jnp.float32)
    state = hybrid_state_shape(cfg, 1, seq, sc)
    tt = jnp.full((1,), 0.5, jnp.float32)

    def step(lat, cond, k, v):
        from repro.core.pipefusion import KVState
        return dit_forward_displaced(params, cfg, ctx, latents=lat,
                                     cond=cond, timesteps=tt,
                                     kv_state=KVState(k, v),
                                     num_patches=2, pp=2)

    with comm.record("displaced") as tr:
        lowered = jax.jit(step).lower(lat, cond, state.k, state.v)
    pipe_events = [e for e in tr.events if e.stream == "pipe"]
    # one hand-off per (patch, stage boundary): 2 patches x 1 boundary
    assert len(pipe_events) == 2, tr.events
    assert all(e.overlaps == "stage compute" for e in pipe_events)
    report = comm.validate(tr, lowered.compile().as_text(), mesh)
    assert report.ok, report.summary()
    assert any(ch.startswith("pipe.") for ch in report.overlapped), report


@pytest.mark.parametrize("k", [1, 2, 3])
def test_distance_k_torus_hop_validates_against_hlo(k, mesh8, rng):
    """Each distance-k hop of the decomposed all-to-all must compile to a
    collective-permute with exactly the intended distance-k route."""
    layout = _layout(4, 1)
    x = jax.random.normal(rng, (8, 16))
    spec = P(SP_AXES)

    def fn(xs):
        return comm.torus_hop(layout, k, xs).wait()

    with comm.record(f"hop{k}") as tr:
        lowered = jax.jit(_smap(fn, mesh8, spec)).lower(x)
    (e,) = tr.events
    assert e.channel == f"torus.hop{k}"
    assert e.perm == tuple(layout.ulysses_stage_perm(k))
    report = comm.validate(tr, lowered.compile().as_text(), mesh8,
                           require_overlap=False)
    assert report.ok, report.summary()
    assert report.hlo_permutes >= 1


@pytest.mark.parametrize("k", [1, 3])
def test_distance_k_torus_hop_validates_under_pallas(k, mesh8, rng):
    """Same distance-k routes through the Pallas channel backend
    (emulation branch, interpret mode): the wire move must still carry the
    intended pairs in HLO and the semaphore schedule must pair up."""
    layout = _layout(4, 1)
    x = jax.random.normal(rng, (8, 16))
    spec = P(SP_AXES)

    def fn(xs):
        return comm.torus_hop(layout, k, xs, backend="pallas",
                              interpret=True).wait()

    with comm.record(f"phop{k}") as tr:
        lowered = jax.jit(_smap(fn, mesh8, spec)).lower(x)
    assert all(e.backend == "pallas" for e in tr.events)
    assert tr.sem_events, "pallas put recorded no semaphore events"
    report = comm.validate(tr, lowered.compile().as_text(), mesh8,
                           require_overlap=False)
    assert report.ok, report.summary()
    sem = comm.validate_semaphores(tr)
    assert sem.ok, sem.summary()


def test_staged_a2a_validates_under_pallas(mesh8, rng):
    """The staged all-to-all Stream program under backend="pallas": every
    stage's route in HLO, a clean semaphore pairing, and value parity with
    the monolithic collective it replaces."""
    layout = _layout(4, 1)
    x = jax.random.normal(rng, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)
    out_spec = P(None, None, SP_AXES, None, None)

    def staged(xs):
        return comm.staged_all_to_all(xs, layout, split_axis=2,
                                      backend="pallas", interpret=True)

    f = jax.shard_map(staged, mesh=mesh8, in_specs=(spec,), out_specs=out_spec,
                      check_vma=False)
    with comm.record("a2a_pallas") as tr:
        lowered = jax.jit(f).lower(x)
    # P_u - 1 = 3 wire stages (the diagonal chunk never leaves the device)
    assert len(tr.events) == 3
    assert all(e.backend == "pallas" for e in tr.events)
    report = comm.validate(tr, lowered.compile().as_text(), mesh8,
                           require_overlap=False)
    assert report.ok, report.summary()
    sem = comm.validate_semaphores(tr)
    assert sem.ok, sem.summary()
    ref = jax.shard_map(lambda xs: monolithic_all_to_all(xs, layout, split_axis=2),
                        mesh=mesh8, in_specs=(spec,), out_specs=out_spec,
                        check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(ref(x)), rtol=1e-6, atol=1e-6)


def test_staged_ungroup_validates_under_pallas(mesh8, rng):
    """The inverse program (a2a.inv) under the Pallas backend round-trips
    values and validates both its routes and its semaphore schedule."""
    layout = _layout(4, 1)
    x = jax.random.normal(rng, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)

    def roundtrip(xs):
        stacked = comm.staged_all_to_all(xs, layout, split_axis=2,
                                         backend="pallas", interpret=True)
        return comm.staged_ungroup(stacked, layout, concat_axis=2,
                                   backend="pallas", interpret=True)

    f = _smap(roundtrip, mesh8, spec)
    with comm.record("rt_pallas") as tr:
        lowered = jax.jit(f).lower(x)
    assert {e.stream for e in tr.events} == {"a2a", "a2a.inv"}
    report = comm.validate(tr, lowered.compile().as_text(), mesh8,
                           require_overlap=False)
    assert report.ok, report.summary()
    sem = comm.validate_semaphores(tr)
    assert sem.ok, sem.summary()
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# hierarchical two-level a2a (DESIGN.md §8.2)
# ---------------------------------------------------------------------------

def _hier_layout(p_u=4, p_r=1):
    """mesh8's SP group is (pod=2, model=2): N=2 machines, so the only
    hier-applicable factorisation is P_u=4 (m_u=2 members per machine)."""
    return GroupLayout(SP_AXES, p_u, p_r, ulysses_outer=True, u_groups=2)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hier_a2a_bit_compatible_with_monolithic(backend, mesh8, rng):
    """Acceptance gate: on the 8-device CPU mesh the hierarchical a2a is
    bit-compatible (<= 1e-5 fp32; exact, being pure routing) with the
    monolithic collective under both channel backends."""
    hier, flat = _hier_layout(), _layout(4, 1)
    x = jax.random.normal(rng, (2, 32, 8, 4)).astype(jnp.float32)
    spec = P(None, SP_AXES, None, None)
    out_spec = P(None, None, SP_AXES, None, None)

    def hier_fn(xs):
        # dispatch happens inside monolithic_all_to_all on u_groups > 1
        return monolithic_all_to_all(xs, hier, split_axis=2,
                                     backend=backend)

    def flat_fn(xs):
        return monolithic_all_to_all(xs, flat, split_axis=2)

    f_h = jax.shard_map(hier_fn, mesh=mesh8, in_specs=(spec,),
                        out_specs=out_spec, check_vma=False)
    f_f = jax.shard_map(flat_fn, mesh=mesh8, in_specs=(spec,),
                        out_specs=out_spec, check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f_h)(x)),
                               np.asarray(jax.jit(f_f)(x)),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hier_roundtrip_and_ungroup(backend, mesh8, rng):
    layout = _hier_layout()
    x = jax.random.normal(rng, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)

    def roundtrip(xs):
        stacked = monolithic_all_to_all(xs, layout, split_axis=2,
                                        backend=backend)
        return ungroup_all_to_all(stacked, layout, concat_axis=2,
                                  backend=backend)

    f = _smap(roundtrip, mesh8, spec)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)), np.asarray(x),
                               rtol=0, atol=0)


def test_hier_a2a_fp8_wire_close_to_exact(mesh8, rng):
    """With fp8 on the inter-machine leg only, the result stays within
    e4m3 mantissa error of the exact exchange (intra leg untouched)."""
    pytest.importorskip("jax.numpy", reason="float8 availability")
    from repro.comm.compress import has_wire_dtype
    if not has_wire_dtype("float8_e4m3fn"):
        pytest.skip("jax build lacks float8")
    layout = _hier_layout()
    x = jax.random.normal(rng, (2, 32, 8, 4)).astype(jnp.float32)
    spec = P(None, SP_AXES, None, None)
    out_spec = P(None, None, SP_AXES, None, None)

    def fp8(xs):
        return monolithic_all_to_all(xs, layout, split_axis=2,
                                     wire_dtype="float8_e4m3fn")

    def exact(xs):
        return monolithic_all_to_all(xs, layout, split_axis=2)

    f8 = jax.shard_map(fp8, mesh=mesh8, in_specs=(spec,), out_specs=out_spec,
                       check_vma=False)
    fx = jax.shard_map(exact, mesh=mesh8, in_specs=(spec,), out_specs=out_spec,
                       check_vma=False)
    got, ref = np.asarray(jax.jit(f8)(x)), np.asarray(jax.jit(fx)(x))
    assert not np.array_equal(got, ref), "fp8 wire did not engage"
    np.testing.assert_allclose(got, ref, rtol=0.08, atol=0.08)


def test_hier_a2a_trace_declares_and_validates_inter_overlap(mesh8, rng):
    """The acceptance trace gate: both legs' hops appear as channel events
    with the intended routes; the inter hops carry an overlap declaration
    that validate() admits against the compiled HLO.  Two tensors go
    through the transform (as Q/K/V do in gather_qkv) — the exchanges are
    mutually independent, which is the compute the declaration names (a
    SINGLE standalone g=2 exchange has no peer and cannot overlap)."""
    layout = _hier_layout()
    kx, ky = jax.random.split(rng)
    x = jax.random.normal(kx, (2, 32, 8, 4))
    y = jax.random.normal(ky, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)
    out_spec = P(None, None, SP_AXES, None, None)

    def fn(xs, ys):
        return (monolithic_all_to_all(xs, layout, split_axis=2),
                monolithic_all_to_all(ys, layout, split_axis=2))

    f = jax.shard_map(fn, mesh=mesh8, in_specs=(spec, spec),
                      out_specs=(out_spec, out_spec), check_vma=False)
    with comm.record("hier") as tr:
        lowered = jax.jit(f).lower(x, y)
    chans = [e.channel for e in tr.events]
    # per tensor: m_u - 1 = 1 fast-leg stage, g - 1 = 1 slow-leg stage
    assert chans == ["hier.a2a.intra1", "hier.a2a.inter1"] * 2, chans
    intra_e, inter_e = tr.events[:2]
    assert intra_e.perm == tuple(layout.ulysses_intra_stage_perm(1))
    assert inter_e.perm == tuple(layout.ulysses_inter_stage_perm(1))
    # the fast leg never crosses the machine boundary
    pod = mesh8.shape["model"]
    for s, d in intra_e.perm:
        assert s // pod == d // pod, intra_e.perm
    assert any(s // pod != d // pod for s, d in inter_e.perm)
    for e in tr.events:
        if e.channel.endswith("inter1"):
            assert e.overlaps, "inter hop must declare its overlap intent"
    report = comm.validate(tr, lowered.compile().as_text(), mesh8)
    assert report.ok, report.summary()
    assert any(ch.startswith("hier.a2a.inter") for ch in report.overlapped), (
        report.overlapped)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_hier_a2a_profiler_measures_inter_hops(backend, mesh8, rng):
    """PR-7 profiler agreement: the executed schedule records the inter
    hops as comm legs whose issue->signal windows exist and whose intent
    tag matches the trace declaration."""
    layout = _hier_layout()
    x = jax.random.normal(rng, (2, 32, 8, 4))
    spec = P(None, SP_AXES, None, None)
    out_spec = P(None, None, SP_AXES, None, None)

    def fn(xs):
        return monolithic_all_to_all(xs, layout, split_axis=2,
                                     backend=backend)

    f = jax.shard_map(fn, mesh=mesh8, in_specs=(spec,), out_specs=out_spec,
                      check_vma=False)
    prof = comm.CommProfiler()
    with comm.profile(prof):
        out = jax.jit(f)(x)
    jax.block_until_ready(out)
    evs = prof.take()
    inter = [e for e in evs if e.meta.channel.startswith("hier.a2a.inter")]
    assert inter, [e.meta.channel for e in evs]
    assert {e.phase for e in inter} >= {"issue", "signal"}
    assert all(e.meta.intent for e in inter
               if e.meta.kind == "comm"), "inter legs lost their intent tag"


def test_hier_attention_matches_flat_end_to_end(mesh8, rng):
    """sp_attention with hier_a2a on vs off: identical O (<= 1e-5 fp32)
    — the full four-transform path through gather_qkv/scatter_o."""
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (2, 32, 4, 16))
    k = jax.random.normal(kk, (2, 32, 4, 16))
    v = jax.random.normal(kv, (2, 32, 4, 16))
    base = SPConfig(strategy="ulysses", sp_axes=SP_AXES,
                    batch_axes=("data",))
    hier = dataclasses.replace(base, hier_a2a=True)

    def run(cfg):
        return jax.jit(lambda *a: sp_attention(
            *a, mesh=mesh8, cfg=cfg))(q, k, v)

    np.testing.assert_allclose(np.asarray(run(hier)), np.asarray(run(base)),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# staged a2a <-> ungroup round-trip property (uneven heads, dtypes, layouts)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("outer", [True, False])
@pytest.mark.parametrize("p_u", [1, 2, 4])
def test_staged_roundtrip_property(p_u, outer, dtype, mesh8, rng):
    """staged_all_to_all ∘ staged_ungroup == identity for uneven head
    chunks (12 heads -> chunks of 3), both element dtypes (pure routing:
    exact even in bf16), every P_u, and both ulysses_outer layouts."""
    layout = GroupLayout(SP_AXES, p_u, 4 // p_u, ulysses_outer=outer)
    x = jax.random.normal(rng, (1, 32, 12, 2)).astype(dtype)
    spec = P(None, SP_AXES, None, None)

    def roundtrip(xs):
        stacked = comm.staged_all_to_all(xs, layout, split_axis=2)
        return comm.staged_ungroup(stacked, layout, concat_axis=2)

    f = _smap(roundtrip, mesh8, spec)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)), np.asarray(x))


@pytest.mark.parametrize("outer", [True, False])
@pytest.mark.parametrize("p_u", [2, 4])
def test_staged_chunk_order_matches_group_positions(p_u, outer, mesh8):
    """The stacked output's source-u ordering IS group_positions': encode
    each element's global sequence position into the input and check
    stacked[j] carries exactly the positions group_positions assigns to
    source j."""
    from repro.core.ulysses import group_positions

    layout = GroupLayout(SP_AXES, p_u, 4 // p_u, ulysses_outer=outer)
    ls = 8  # 32 global / 4 SP devices
    x = jnp.broadcast_to(jnp.arange(32, dtype=jnp.float32)[None, :, None,
                                                           None],
                         (1, 32, p_u, 1))
    spec = P(None, SP_AXES, None, None)

    def check(xs):
        stacked = comm.staged_all_to_all(xs, layout, split_axis=2)
        _, r = layout.my_coords()
        want = group_positions(layout, ls, r).reshape(p_u, ls)
        got = stacked[:, 0, :, 0, 0]  # [P_u, Ls] of encoded positions
        return jnp.max(jnp.abs(got - want)).reshape(1)

    f = jax.shard_map(check, mesh=mesh8, in_specs=(spec,),
                      out_specs=P(SP_AXES), check_vma=False)
    assert np.asarray(jax.jit(f)(x)).max() == 0.0
