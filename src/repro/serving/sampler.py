"""Flow-matching Euler sampler for DiT serving (paper Figure 1 pipeline).

One sampling step = one full DiT forward (velocity prediction) — this is
the unit the paper benchmarks ("latency of one sampling step").  The
sampler integrates x_t from t=1 (noise) to t=0 (data) with uniform Euler
steps; the toy linear VAE decode is the stubbed frontend inverse
(DESIGN.md §6).

Beyond the paper, the sampler composes two extra parallel axes with SP
(DESIGN.md §7):

  * **CFG parallelism** (``SamplerConfig.cfg_parallel``): with guidance
    enabled, the k guidance branches are stacked on the batch dim and —
    when the mesh carries ``SPConfig.cfg_axis`` — sharded across a k-way
    mesh axis, so each mesh slice runs one branch.  The branches recombine
    with a single psum-style weighted sum of the velocities
    (``v = Σ_i w_i·v_i``), the only cross-branch communication of the
    whole step.  The classic pair is k = 2 with weights ``(g, 1-g)``;
    ``cfg_weights`` generalises to negative prompts and multi-conditioning
    stacks (k > 2), with per-branch conditioning passed as a stacked
    ``[k, B, text_tokens, cond_width]`` tensor.
  * **Displaced patch pipelining** (``SamplerConfig.pipeline``): after
    ``warmup_steps`` synchronous steps, each step runs the PipeFusion
    forward (models/dit.py: ``dit_forward_displaced``) reusing
    one-step-stale KV for non-resident patches; the sampler threads the
    per-layer KVState across steps.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core.pipefusion import KVState, PipelineConfig, init_kv_state, kv_drift
from ..models import ParallelContext
from ..models.dit import LATENT_CHANNELS, dit_forward, dit_forward_displaced


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    num_steps: int = 20
    guidance_scale: float = 1.0  # >1 enables classifier-free guidance
    # Per-branch guidance weights for degree-k CFG (ROADMAP: k > 2 stacks).
    # None = classic 2-way (guidance_scale, 1 - guidance_scale).  With k > 2
    # (or a negative prompt at k = 2) every branch's conditioning must be
    # supplied explicitly as a stacked [k, B, text_tokens, cond_width] cond
    # (zeros rows = unconditional branches).
    cfg_weights: tuple[float, ...] | None = None
    # hybrid parallelism (DESIGN.md §7); both compose with any SP strategy
    cfg_parallel: bool = False  # evaluate the CFG branches on the cfg axis
    pipeline: PipelineConfig | None = None  # patch-level pipelining

    @property
    def guided(self) -> bool:
        return self.guidance_scale != 1.0 or self.cfg_weights is not None

    @property
    def branch_weights(self) -> tuple[float, ...]:
        if self.cfg_weights is not None:
            return tuple(self.cfg_weights)
        return (self.guidance_scale, 1.0 - self.guidance_scale)

    @property
    def cfg_degree(self) -> int:
        return len(self.branch_weights)

    @property
    def branches(self) -> int:
        """Forwards a step takes of each request: its guidance branches."""
        return self.cfg_degree if self.guided else 1

    @property
    def pipelined(self) -> bool:
        return self.pipeline is not None and self.pipeline.enabled


def _cfg_recombine(v_all: jax.Array, batch: int,
                   weights: tuple[float, ...]) -> jax.Array:
    """The single cross-branch exchange: v = Σ_i w_i·v_i.

    Written as one weighted sum over the stacked branch dim (not the
    ``v_u + g (v_c - v_u)`` algebra) so with the branches sharded over the
    cfg axis it lowers to exactly one psum-sized collective of the
    velocity tensor, for any guidance degree k.
    """
    k = len(weights)
    v_br = v_all.reshape(k, batch, *v_all.shape[1:])
    w = jnp.asarray(weights, v_all.dtype).reshape(k, *([1] * (v_all.ndim)))
    return jnp.sum(w * v_br, axis=0)


def _branch_conds(cond: jax.Array, k: int) -> jax.Array:
    """Per-branch conditioning [k, B, C, d] from the user-facing ``cond``:
    stacked explicit branches, or the classic (cond, zeros) pair."""
    if cond.ndim == 4:
        assert cond.shape[0] == k, (
            f"stacked cond has {cond.shape[0]} branches, guidance degree {k}")
        return cond
    assert k == 2, (
        f"guidance degree {k} needs explicit stacked [k, B, C, d] cond")
    return jnp.stack([cond, jnp.zeros_like(cond)], axis=0)


def _stack_cfg_branches(x_t, cond, k: int):
    """[B,...] -> [kB,...]: branch i occupies rows [i·B, (i+1)·B)."""
    conds = _branch_conds(cond, k)
    return (jnp.concatenate([x_t] * k, axis=0),
            jnp.concatenate(list(conds), axis=0))


def _ctx_for(ctx: ParallelContext, sc: SamplerConfig) -> ParallelContext:
    """Drop the cfg mesh axis from the sharding specs unless this sampler
    config actually stacks the CFG pair — otherwise the un-doubled batch
    cannot be sharded over the 2-way cfg axis (shard_map divisibility)."""
    if ctx.sp.cfg_axis and not (sc.guided and sc.cfg_parallel):
        return dataclasses.replace(
            ctx, sp=dataclasses.replace(ctx.sp, cfg_axis=None))
    return ctx


def sample_step(params, cfg: ModelConfig, ctx: ParallelContext,
                x_t: jax.Array, cond: jax.Array, t: jax.Array,
                dt: jax.Array, sc: SamplerConfig) -> jax.Array:
    """One Euler step x_{t-dt} = x_t - dt * v(x_t, t)."""
    ctx = _ctx_for(ctx, sc)
    b = x_t.shape[0]
    tt = jnp.full((b,), t, jnp.float32)
    if sc.guided and sc.cfg_parallel:
        k = sc.cfg_degree
        lat_k, cond_k = _stack_cfg_branches(x_t, cond, k)
        v_all = dit_forward(params, cfg, ctx, latents=lat_k, cond=cond_k,
                            timesteps=jnp.concatenate([tt] * k))
        v = _cfg_recombine(v_all, b, sc.branch_weights)
        return x_t - dt * v.astype(x_t.dtype)
    if sc.guided and sc.cfg_weights is not None:
        # sequential general-degree guidance: one forward per branch,
        # recombined with the same weighted sum as the parallel path
        conds = _branch_conds(cond, sc.cfg_degree)
        v = None
        for w, c in zip(sc.branch_weights, conds):
            vb = dit_forward(params, cfg, ctx, latents=x_t, cond=c,
                             timesteps=tt)
            v = w * vb if v is None else v + w * vb
        return x_t - dt * v.astype(x_t.dtype)
    v = dit_forward(params, cfg, ctx, latents=x_t, cond=cond, timesteps=tt)
    if sc.guided:
        v_un = dit_forward(params, cfg, ctx, latents=x_t,
                           cond=jnp.zeros_like(cond), timesteps=tt)
        v = v_un + sc.guidance_scale * (v - v_un)
    return x_t - dt * v.astype(x_t.dtype)


# ---------------------------------------------------------------------------
# hybrid (cfg-parallel × patch-pipelined) stepping with threaded KV state
# ---------------------------------------------------------------------------

def hybrid_state_shape(cfg: ModelConfig, batch: int, seq_len: int,
                       sc: SamplerConfig) -> KVState:
    """Zero KVState matching what the hybrid steps thread (all k guidance
    branches included when cfg-parallel)."""
    b = sc.cfg_degree * batch if (sc.guided and sc.cfg_parallel) else batch
    return init_kv_state(cfg.n_layers, b, cfg.text_tokens + seq_len,
                         cfg.n_kv_heads, cfg.resolved_head_dim,
                         jnp.dtype(cfg.dtype))


def hybrid_sample_step(params, cfg: ModelConfig, ctx: ParallelContext,
                       x_t: jax.Array, cond: jax.Array, t: jax.Array,
                       dt: jax.Array, sc: SamplerConfig, state: KVState,
                       *, warm: bool
                       ) -> tuple[jax.Array, KVState, dict[str, jax.Array]]:
    """One Euler step that also threads the displaced-pipeline KV state.

    ``warm`` (static): True runs the fully-synchronous forward — identical
    computation to ``sample_step``'s x-path — while capturing per-layer KV;
    False runs the PipeFusion displaced forward against ``state``.

    The third return is the per-step metrics dict: ``kv_drift`` is the
    batch-mean staleness measure ``PipelineConfig.resync_every`` bounds
    (core/pipefusion.kv_drift) and ``kv_drift_per_request`` its [B]
    per-request breakdown (guidance branches of one request folded
    together); both are 0 for warm steps.
    """
    assert sc.pipelined
    ctx = _ctx_for(ctx, sc)
    pipe = sc.pipeline
    b = x_t.shape[0]
    tt = jnp.full((b,), t, jnp.float32)
    if sc.guided and sc.cfg_parallel:
        lat_in, cond_in = _stack_cfg_branches(x_t, cond, sc.cfg_degree)
        tt_in = jnp.concatenate([tt] * sc.cfg_degree)
    elif sc.guided:
        raise NotImplementedError(
            "pipelined sampling with sequential CFG would need one KV "
            "state per branch; enable cfg_parallel (works on any mesh) "
            "instead")
    else:
        lat_in, cond_in, tt_in = x_t, cond, tt

    if warm:
        v_out, state = dit_forward(params, cfg, ctx, latents=lat_in,
                                   cond=cond_in, timesteps=tt_in,
                                   return_layer_kv=True)
        per_req = jnp.zeros((b,), jnp.float32)
    else:
        prev = state
        v_out, state = dit_forward_displaced(
            params, cfg, ctx, latents=lat_in, cond=cond_in, timesteps=tt_in,
            kv_state=state, num_patches=pipe.patches, pp=pipe.pp)
        per_req = kv_drift(prev, state, per_item=True).astype(jnp.float32)
        if sc.guided and sc.cfg_parallel:
            # branch rows of one request fold into that request's drift
            per_req = per_req.reshape(sc.cfg_degree, b).mean(axis=0)
    if sc.guided and sc.cfg_parallel:
        v = _cfg_recombine(v_out, b, sc.branch_weights)
    else:
        v = v_out
    metrics = {"kv_drift": per_req.mean(), "kv_drift_per_request": per_req}
    return x_t - dt * v.astype(x_t.dtype), state, metrics


def sample(params, cfg: ModelConfig, ctx: ParallelContext, *,
           key: jax.Array, batch: int, seq_len: int, cond: jax.Array,
           sc: SamplerConfig = SamplerConfig(),
           step_fn=None, metrics: list[dict] | None = None,
           drift_policy=None,
           drift_thresholds: list[float | None] | None = None,
           interrupt=None, tracker=None) -> jax.Array:
    """Full sampling loop; returns final latents [B, T, LATENT_CHANNELS].

    With ``sc.pipeline`` set, the loop threads the displaced-pipeline KV
    state: the first ``warmup_steps`` steps run synchronously, then
    displaced (PipeFusion) with a periodic synchronous re-sync every
    ``resync_every`` steps.  Passing a ``drift_policy`` (sched.DriftPolicy)
    replaces that static period with threshold-triggered resync: a step
    runs warm exactly when the previous step's per-request ``kv_drift``
    crossed the request's bound (``drift_thresholds``, one entry per batch
    row, None entries fall back to the policy default) — reading the drift
    on the host costs one device sync per step.  A custom ``step_fn``
    bypasses all of that.

    The loop is **step-granular** (DESIGN.md §10):

      * Passing a ``metrics`` list collects one per-step dict (``step``,
        ``warm``, ``kv_drift``, ``t_step_s``).  ``t_step_s`` is that
        step's own wall clock — the loop blocks on the step's outputs
        before stamping it, so resync (warm) steps and displaced steps
        are timed individually instead of aggregating into one number.
        This is what the online calibrator and the preemption policy
        consume; without ``metrics`` no per-step sync is paid.
      * ``interrupt``, called as ``interrupt(step_index)`` after every
        completed step, stops the loop early when it returns True and
        the current latents are returned as-is — the hook an embedding
        engine uses to park a batch between steps.
      * ``tracker`` (serving.metrics, DESIGN.md §11) publishes the same
        per-step series (``sampler.t_step_s``, ``sampler.kv_drift``) to
        a metrics sink.  A *persistent* sink (JSONL / recording) turns
        timing on by itself; an aggregate-only sink only collects what
        the ``metrics`` list already paid for.
    """
    x = jax.random.normal(key, (batch, seq_len, LATENT_CHANNELS), cfg.dtype)
    dt = 1.0 / sc.num_steps
    timed = metrics is not None or (tracker is not None
                                    and tracker.persistent)

    def stamp(i: int, outputs, extra_fn, t0: float) -> None:
        """Stop the step clock, THEN materialise extras and emit.

        ``t_step_s`` is captured the instant the step's outputs are ready:
        everything instrumentation-side — drift-float materialisation
        (``extra_fn`` is lazy), metrics appends, tracker/span emission —
        happens after the clock stops, so a slow sink cannot inflate the
        wall clocks the OnlineCalibrator fits (test_sampler.py pins this
        with a deliberately slow tracker)."""
        if not timed:
            return
        jax.block_until_ready(outputs)
        t_step = time.perf_counter() - t0
        extra = extra_fn() if callable(extra_fn) else extra_fn
        if metrics is not None:
            metrics.append({"step": i, "t_step_s": t_step, **extra})
        if tracker is not None:
            tracker.log("sampler.t_step_s", t_step, step=i,
                        tags={"warm": extra["warm"]}
                        if "warm" in extra else None)
            if "kv_drift" in extra:
                tracker.log("sampler.kv_drift", extra["kv_drift"], step=i)
            if tracker.persistent:
                tracker.span_event(
                    "sampler.step", t0 - tracker.epoch, t_step, step=i,
                    tags={"warm": extra["warm"]} if "warm" in extra
                    else None)

    if step_fn is not None:
        for i in range(sc.num_steps):
            t0 = time.perf_counter()
            x = step_fn(x, cond, 1.0 - i * dt)
            stamp(i, x, {}, t0)
            if interrupt is not None and interrupt(i):
                return x
        return x
    if not sc.pipelined:
        for i in range(sc.num_steps):
            t0 = time.perf_counter()
            x = sample_step(params, cfg, ctx, x, cond, 1.0 - i * dt, dt, sc)
            stamp(i, x, {}, t0)
            if interrupt is not None and interrupt(i):
                return x
        return x
    thresholds = drift_thresholds or [None] * batch
    use_drift = drift_policy is not None and drift_policy.engaged(thresholds)
    last_drift: list[float] | None = None
    state = hybrid_state_shape(cfg, batch, seq_len, sc)
    for i in range(sc.num_steps):
        if use_drift:
            warm = drift_policy.warm(sc.pipeline, i, last_drift, thresholds)
        else:
            warm = sc.pipeline.warm_step(i)
        t0 = time.perf_counter()
        x, state, m = hybrid_sample_step(params, cfg, ctx, x, cond,
                                         1.0 - i * dt, dt, sc, state,
                                         warm=warm)
        if timed:
            # stamp FIRST (clock stops at output-ready), then materialise
            # the drift floats lazily inside stamp — the per-step host
            # sync is still only paid when a drift bound or the metrics
            # list is configured (the PR-3 contract), and instrumentation
            # cost stays out of the timed region (satellite fix, PR 7)
            stamp(i, (x, state), lambda: {
                "warm": warm,
                "kv_drift": float(m["kv_drift"]),
                "kv_drift_per_request": [
                    float(d) for d in m["kv_drift_per_request"]],
            }, t0)
        if use_drift:
            per = m["kv_drift_per_request"]
            last_drift = [float(per[j]) for j in range(batch)]
        if interrupt is not None and interrupt(i):
            return x
    return x


def toy_vae_decode(latents: jax.Array, out_channels: int = 3,
                   patch: int = 2) -> jax.Array:
    """Stub VAE decoder: fixed linear map latent tokens -> pixel patches.
    [B, T, C] -> [B, T * patch**2, out_channels]."""
    b, t, c = latents.shape
    key = jax.random.PRNGKey(42)  # fixed decoder
    w = jax.random.normal(key, (c, patch * patch * out_channels), latents.dtype)
    px = jnp.einsum("btc,cp->btp", latents, w) / (c ** 0.5)
    return px.reshape(b, t * patch * patch, out_channels)
