"""Hybrid parallelism (DESIGN.md §7): displaced patch pipelining
(PipeFusion), CFG parallelism, and the (cfg, pp, P_u, P_r) planner.

Runs on 1 device (strategy="full"); the 8-fake-device composition with
swift_torus lives in tests/multidevice/test_hybrid.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, get_reduced
from repro.core import PipelineConfig, SPConfig, plan_hybrid
from repro.core.pipefusion import patch_slices, stage_layers
from repro.models import ParallelContext, get_model
from repro.models.dit import dit_forward, dit_forward_displaced
from repro.serving import DiTRequest, DiTServer, SamplerConfig, sample

SP = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
SEQ = 32


@pytest.fixture(scope="module")
def dit_setup():
    cfg = dataclasses.replace(get_reduced("flux-12b"), dtype="float32")
    bundle = get_model(cfg)
    params, _ = bundle.init(cfg, jax.random.PRNGKey(0), 1)
    # de-degenerate the adaLN-zero init (a freshly-initialised DiT is the
    # identity, which would make every displaced-vs-reference check vacuous)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(99), len(leaves))
    leaves = [l + 0.05 * jax.random.normal(k, l.shape, l.dtype)
              for l, k in zip(leaves, keys)]
    return cfg, jax.tree.unflatten(treedef, leaves)


@pytest.fixture(scope="module")
def cond(dit_setup):
    cfg, _ = dit_setup
    return jax.random.normal(jax.random.PRNGKey(1), (1, cfg.text_tokens, cfg.cond_width),
                             jnp.float32)


# ---------------------------------------------------------------------------
# (a) warm steps bitwise; displaced steps within tolerance
# ---------------------------------------------------------------------------

def test_all_warm_pipeline_matches_reference_bitwise(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(7)
    ref = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                 sc=SamplerConfig(num_steps=3))
    warm = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                  sc=SamplerConfig(num_steps=3,
                                   pipeline=PipelineConfig(pp=2, warmup_steps=3)))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(warm))


def test_displaced_steps_close_but_not_identical(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(7)
    ref = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                 sc=SamplerConfig(num_steps=4))
    disp = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                  sc=SamplerConfig(num_steps=4,
                                   pipeline=PipelineConfig(pp=2, warmup_steps=1)))
    assert bool(jnp.all(jnp.isfinite(disp)))
    diff = float(jnp.max(jnp.abs(ref - disp)))
    scale = float(jnp.max(jnp.abs(ref)))
    assert diff < 0.05 * scale, (diff, scale)  # one-step-stale approximation
    assert diff > 0.0  # the displaced path genuinely ran


def test_displaced_forward_with_fresh_state_matches_reference(dit_setup, cond,
                                                              mesh1):
    """stale == fresh  =>  displaced forward == full forward (up to the
    partial-merge float association)."""
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    lat = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, 64), jnp.float32)
    tt = jnp.full((1,), 0.5, jnp.float32)
    v_ref, state = dit_forward(params, cfg, ctx, latents=lat, cond=cond,
                               timesteps=tt, return_layer_kv=True)
    for n_patches in (2, 4):
        v_disp, new_state = dit_forward_displaced(
            params, cfg, ctx, latents=lat, cond=cond, timesteps=tt,
            kv_state=state, num_patches=n_patches, pp=2)
        np.testing.assert_allclose(np.asarray(v_disp), np.asarray(v_ref),
                                   atol=5e-5, rtol=1e-4)
        # the state write-back covers every row: fresh == stale here
        np.testing.assert_allclose(np.asarray(new_state.k),
                                   np.asarray(state.k), atol=5e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# (b) cfg-parallel sampling == sequential CFG
# ---------------------------------------------------------------------------

def test_cfg_parallel_matches_sequential_cfg(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(11)
    cond2 = jnp.tile(cond, (2, 1, 1))
    seq = sample(params, cfg, ctx, key=key, batch=2, seq_len=SEQ, cond=cond2,
                 sc=SamplerConfig(num_steps=3, guidance_scale=4.0))
    par = sample(params, cfg, ctx, key=key, batch=2, seq_len=SEQ, cond=cond2,
                 sc=SamplerConfig(num_steps=3, guidance_scale=4.0,
                                  cfg_parallel=True))
    np.testing.assert_allclose(np.asarray(seq), np.asarray(par),
                               atol=1e-4, rtol=1e-4)


def test_cfg_parallel_composes_with_pipeline(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(13)
    ref = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                 sc=SamplerConfig(num_steps=4, guidance_scale=4.0))
    hyb = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                 sc=SamplerConfig(num_steps=4, guidance_scale=4.0,
                                  cfg_parallel=True,
                                  pipeline=PipelineConfig(pp=2, warmup_steps=1)))
    assert bool(jnp.all(jnp.isfinite(hyb)))
    diff = float(jnp.max(jnp.abs(ref - hyb)))
    assert diff < 0.05 * float(jnp.max(jnp.abs(ref))), diff


def test_cfg_weights_match_classic_pair(dit_setup, cond, mesh1):
    """cfg_weights=(g, 1-g) is the classic CFG pair, parallel or not."""
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(17)
    classic = sample(params, cfg, ctx, key=key, batch=2, seq_len=SEQ,
                     cond=jnp.tile(cond, (2, 1, 1)),
                     sc=SamplerConfig(num_steps=3, guidance_scale=4.0,
                                      cfg_parallel=True))
    weighted = sample(params, cfg, ctx, key=key, batch=2, seq_len=SEQ,
                      cond=jnp.tile(cond, (2, 1, 1)),
                      sc=SamplerConfig(num_steps=3,
                                       cfg_weights=(4.0, -3.0),
                                       cfg_parallel=True))
    np.testing.assert_allclose(np.asarray(classic), np.asarray(weighted),
                               atol=1e-5, rtol=1e-5)


def test_cfg_degree_3_weighted_recombine(dit_setup, mesh1):
    """k=3 guidance (two conditionings + uncond) == the hand-computed
    weighted sum of three separate forwards, in both the cfg-parallel and
    the sequential general-degree paths (ROADMAP k>2 item)."""
    from repro.models.dit import dit_forward
    from repro.serving.sampler import sample_step

    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    weights = (3.0, 1.5, -3.5)  # sums to 1: Σ g_i cond_i + (1-Σ g_i) uncond
    c1 = jax.random.normal(jax.random.PRNGKey(21),
                           (1, cfg.text_tokens, cfg.cond_width), jnp.float32)
    c2 = jax.random.normal(jax.random.PRNGKey(22),
                           (1, cfg.text_tokens, cfg.cond_width), jnp.float32)
    conds = jnp.stack([c1, c2, jnp.zeros_like(c1)], axis=0)  # [3, B, C, d]
    x = jax.random.normal(jax.random.PRNGKey(23), (1, SEQ, 64), jnp.float32)
    tt = jnp.full((1,), 0.7, jnp.float32)
    # hand-computed reference
    v_ref = sum(
        w * dit_forward(params, cfg, ctx, latents=x, cond=c, timesteps=tt)
        for w, c in zip(weights, [c1, c2, jnp.zeros_like(c1)]))
    x_ref = x - 0.1 * v_ref
    for par in (True, False):
        sc = SamplerConfig(num_steps=10, cfg_weights=weights,
                           cfg_parallel=par)
        assert sc.cfg_degree == 3 and sc.guided
        x_new = sample_step(params, cfg, ctx, x, conds, jnp.float32(0.7),
                            jnp.float32(0.1), sc)
        np.testing.assert_allclose(np.asarray(x_new), np.asarray(x_ref),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# (b2) staleness control: resync_every + the surfaced kv drift metric
# ---------------------------------------------------------------------------

def test_warm_step_schedule():
    p = PipelineConfig(pp=2, warmup_steps=2, resync_every=3)
    assert [p.warm_step(i) for i in range(9)] == [
        True, True, False, False, True, False, False, True, False]
    p0 = PipelineConfig(pp=2, warmup_steps=1)  # never re-sync (PipeFusion)
    assert [p0.warm_step(i) for i in range(4)] == [True, False, False, False]
    p1 = PipelineConfig(pp=2, warmup_steps=1, resync_every=1)
    assert all(p1.warm_step(i) for i in range(4))  # every step synchronous


def test_resync_every_step_matches_reference_bitwise(dit_setup, cond, mesh1):
    """resync_every=1 forces every step synchronous => identical to the
    plain sampler, staleness fully eliminated."""
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(7)
    ref = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                 sc=SamplerConfig(num_steps=4))
    resync = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ,
                    cond=cond,
                    sc=SamplerConfig(num_steps=4,
                                     pipeline=PipelineConfig(
                                         pp=2, warmup_steps=1,
                                         resync_every=1)))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(resync))


def test_periodic_resync_tightens_displaced_error(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    key = jax.random.PRNGKey(7)
    ref = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ, cond=cond,
                 sc=SamplerConfig(num_steps=6))

    def err(resync):
        out = sample(params, cfg, ctx, key=key, batch=1, seq_len=SEQ,
                     cond=cond,
                     sc=SamplerConfig(num_steps=6,
                                      pipeline=PipelineConfig(
                                          pp=2, warmup_steps=1,
                                          resync_every=resync)))
        return float(jnp.max(jnp.abs(out - ref)))

    assert err(2) <= err(0) + 1e-7  # periodic re-sync never hurts


def test_kv_drift_per_item_isolates_batch_elements():
    """The per-request drift breakdown must not average one request's
    staleness into another's (the serving policy acts per request)."""
    from repro.core.pipefusion import KVState, kv_drift

    k = jnp.ones((2, 3, 4, 2, 2))  # [L, B=3, T, H, D]
    old = KVState(k=k, v=k)
    new_k = k.at[:, 1].add(1.0)  # only batch element 1 drifts
    new = KVState(k=new_k, v=k)
    per = kv_drift(old, new, per_item=True)
    assert per.shape == (3,)
    assert float(per[0]) == 0.0 and float(per[2]) == 0.0
    assert float(per[1]) > 0.0
    scalar = kv_drift(old, new)
    assert 0.0 < float(scalar) < float(per[1])


def test_sampler_surfaces_kv_drift(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    metrics: list[dict] = []
    sample(params, cfg, ctx, key=jax.random.PRNGKey(5), batch=1, seq_len=SEQ,
           cond=cond,
           sc=SamplerConfig(num_steps=4,
                            pipeline=PipelineConfig(pp=2, warmup_steps=1,
                                                    resync_every=2)),
           metrics=metrics)
    assert [m["step"] for m in metrics] == [0, 1, 2, 3]
    assert [m["warm"] for m in metrics] == [True, False, True, False]
    assert all(m["kv_drift"] == 0.0 for m in metrics if m["warm"])
    displaced = [m["kv_drift"] for m in metrics if not m["warm"]]
    assert displaced and all(d > 0.0 for d in displaced)
    assert all(len(m["kv_drift_per_request"]) == 1 for m in metrics)


def test_pipelined_sequential_cfg_rejected(dit_setup, cond, mesh1):
    cfg, params = dit_setup
    ctx = ParallelContext(mesh1, SP, "prefill")
    with pytest.raises(NotImplementedError):
        sample(params, cfg, ctx, key=jax.random.PRNGKey(0), batch=1,
               seq_len=SEQ, cond=cond,
               sc=SamplerConfig(num_steps=2, guidance_scale=4.0,
                                pipeline=PipelineConfig(pp=2, warmup_steps=1)))


# ---------------------------------------------------------------------------
# engine drive
# ---------------------------------------------------------------------------

def test_dit_server_runs_hybrid_sampler(dit_setup, mesh1):
    cfg, params = dit_setup
    srv = DiTServer(params, cfg, mesh1, SP,
                    sampler=SamplerConfig(num_steps=3, guidance_scale=3.0,
                                          cfg_parallel=True,
                                          pipeline=PipelineConfig(
                                              pp=2, warmup_steps=1)),
                    max_batch=2)
    for i in range(3):
        srv.submit(DiTRequest(rid=i, seq_len=SEQ))
    results = srv.serve()
    assert sorted(r.rid for r in results) == [0, 1, 2]
    for r in results:
        assert r.latents.shape == (SEQ, 64)
        assert bool(jnp.all(jnp.isfinite(r.latents)))


# ---------------------------------------------------------------------------
# (c) hybrid planner over the seed model zoo
# ---------------------------------------------------------------------------

def test_plan_hybrid_valid_for_all_seed_configs():
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        if cfg.attention_free:
            continue
        for n, m in ((2, 8), (4, 8), (2, 16)):
            for cfg_par in (False, True):
                for pp in (1, 2):
                    h = plan_hybrid(n, m, cfg.n_heads, cfg.n_kv_heads,
                                    cfg_parallel=cfg_par, pp=pp)
                    h.validate()
                    assert h.total_devices == n * m, (arch, n, m)
                    heads = min(cfg.n_heads, cfg.n_kv_heads)
                    assert heads % h.sp.p_ulysses == 0, (arch, h)


def test_plan_hybrid_prefers_slow_boundary():
    h = plan_hybrid(4, 8, 24, cfg_parallel=True, pp=2)
    assert h.cfg_machines == 2 and h.pp_machines == 2  # machines consumed
    assert h.sp.n_machines == 1  # SP stays inside the machine
    h2 = plan_hybrid(1, 8, 24, cfg_parallel=True, pp=2)
    assert h2.cfg_machines == 1 and h2.pp_machines == 1  # chips consumed
    assert h2.sp.sp_degree == 2


def test_plan_hybrid_rejects_bad_factorisations():
    with pytest.raises(ValueError):
        plan_hybrid(1, 4, 8, cfg_parallel=True, pp=4)  # 8 > 4 devices
    with pytest.raises(ValueError):
        plan_hybrid(2, 8, 24, pp=3, n_layers=32)  # 3 does not divide 32


def test_hybrid_latency_model_wins_in_comm_bound_regime():
    """The analytical model predicts the hybrid plan beats SP-only at equal
    device count where per-layer inter-machine a2a is exposed (the
    medium-resolution serving bucket), and never wins by magic FLOPs
    (compute terms match)."""
    from repro.core import plan
    from repro.core.comm_model import (
        LayerWorkload, hybrid_step_latency, sp_step_latency)

    wl = LayerWorkload(batch=1, seq=4_096, heads=24, head_dim=128)
    sp_only = plan(4, 8, wl.heads)
    base = sp_step_latency(sp_only, wl, n_layers=96, guided=True)
    h = plan_hybrid(4, 8, wl.heads, cfg_parallel=True, pp=2, n_layers=96)
    hyb = hybrid_step_latency(h, wl, n_layers=96, guided=True)
    assert hyb["t_step"] < base["t_step"]
    assert hyb["inter_elems_step"] < base["inter_elems_step"]


def test_patch_and_stage_partitions():
    assert patch_slices(256, 64, 2) == [(0, 288), (288, 32)]
    assert stage_layers(96, 4) == [(0, 24), (24, 24), (48, 24), (72, 24)]
    with pytest.raises(AssertionError):
        patch_slices(256, 30, 4)  # 30 tokens don't split into 4 patches
    with pytest.raises(AssertionError):
        stage_layers(10, 4)
