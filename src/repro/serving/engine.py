"""Serving engines.

DiTServer — the paper's scenario: requests ask for an image/video at a
given latent sequence length; the SLA-aware request scheduler
(serving/sched, DESIGN.md §9) buckets them by latent length, admits
across buckets against per-request deadlines, and memoizes one compiled
step per bucket shape; the flow-matching sampler runs with the configured
SP strategy and results stream back.

ARServer — autoregressive decode for the LM-family assigned archs:
slot-based continuous batching (fixed B decode slots; prefill on admit;
every engine tick advances all active slots one token through the
sequence-sharded KV cache).  Slot admission is priority-ordered with
aging (shared with the DiT scheduler's starvation accounting), so no
request can be bypassed indefinitely.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp

from ..comm import CommProfiler, emit_leg_spans
from ..comm import profile as comm_profile
from ..configs.base import ModelConfig
from ..core import SPConfig, plan_hybrid
from ..core.strategy import attention_lowering
from ..core.comm_model import NetworkModel
from ..models import ParallelContext, get_model, param_shardings
from ..models.dit import LATENT_CHANNELS
from .metrics import Tracker, profiler_annotation
from .sampler import (
    SamplerConfig,
    hybrid_sample_step,
    hybrid_state_shape,
    sample_step,
)
from .sched import (
    ArrivalForecaster,
    ControlConfig,
    DriftPolicy,
    OnlineCalibrator,
    PlanCache,
    PlanChoice,
    RequestScheduler,
    SchedConfig,
    aged_priority,
    steady_t_step,
)


# ---------------------------------------------------------------------------
# DiT serving (paper §5 workloads)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DiTRequest:
    rid: int
    seq_len: int  # latent tokens (resolution / duration proxy)
    # [cfg.text_tokens, cfg.cond_width] text embedding (stub); None = zeros
    cond: jax.Array | None = None
    submitted: float = 0.0
    # SLA: seconds from submission to deadline; None = best-effort.  The
    # admission policy scores deadline slack with the comm model's
    # predicted batch latency (DESIGN.md §9).
    sla: float | None = None
    # per-request KV-staleness bound for the displaced pipeline; crossing
    # it triggers a resync step (None = the server DriftPolicy's default)
    drift_threshold: float | None = None
    # times this request's batch was parked by the preemption policy
    # (maintained by the engine; requeued requests keep their submitted
    # stamp, so accrued starvation age survives a park)
    preemptions: int = 0


@dataclasses.dataclass
class DiTResult:
    rid: int
    latents: jax.Array
    latency: float
    sampling_steps: int
    # per-step KV staleness trajectory of the displaced pipeline (empty for
    # non-pipelined sampling); see core/pipefusion.kv_drift
    kv_drift: list[float] = dataclasses.field(default_factory=list)
    # warm steps the drift policy injected after warmup (0 under the
    # static resync_every schedule)
    resyncs: int = 0
    # whether the request's deadline (submitted + sla) was met
    sla_met: bool = True
    # per-step wall clocks of the FINAL (completing) run of this
    # request's batch (empty unless the control loop measures steps) —
    # step-granular latencies, not one aggregate over resyncs
    step_times: list[float] = dataclasses.field(default_factory=list)
    # times the request's batch was parked before completing
    preemptions: int = 0


class DiTServer:
    """Batched DiT sampling over the hybrid-parallel mesh (DESIGN.md §7).

    Request intake and batching are delegated to the scheduler subsystem
    (DESIGN.md §9): ``submit`` feeds the bucketer, ``run_once`` asks the
    admission policy for the next (bucket, batch) under SLA/starvation
    rules, and compiled steps come from the plan cache (one trace per
    bucket shape).  Beyond plain SP the server drives two optional extra
    axes:

      * ``sampler.cfg_parallel`` — the CFG branches are evaluated on the
        ``sp.cfg_axis`` slices of the mesh (one psum-style recombine per
        step).
      * ``sampler.pipeline`` — displaced patch pipelining: the server jits
        warm/displaced step variants per (batch, seq) bucket and threads
        the per-layer stale-KV state across the sampling loop.  When the
        mesh carries ``sp.pp_axis`` and ``param_axes`` is given, the
        stacked DiT block weights are sharded over the pipe axis, so each
        stage holds n_layers / pp blocks.  The per-bucket plan choice
        co-selects the patch count for that bucket's latent length.
    """

    def __init__(self, params, cfg: ModelConfig, mesh, sp: SPConfig,
                 sampler: SamplerConfig = SamplerConfig(),
                 max_batch: int = 4, param_axes=None,
                 sched: SchedConfig | None = None,
                 drift: DriftPolicy | None = None,
                 net: NetworkModel | None = None,
                 control: ControlConfig | None = None,
                 tracker: Tracker | None = None,
                 profile: bool = False):
        self.params = params
        self.cfg = cfg
        self.ctx = ParallelContext(mesh, sp, "prefill")
        self.sampler = sampler
        # span-level runtime profiling (DESIGN.md §12): with ``profile``
        # set, step compilation happens under a comm-profiler context (so
        # every channel put/wait and marked compute block carries runtime
        # observation callbacks), the step loop emits ``engine.step``
        # spans, and each admission's device-side leg events are drained
        # into the tracker as ``comm.*`` spans
        self.profiler = CommProfiler() if profile else None
        # one metrics sink for the whole engine (DESIGN.md §11): the plan
        # cache, scheduler, calibrator and step loop all publish here.
        # The default aggregate-only Tracker keeps the legacy counter
        # attributes readable at zero retention cost; pass a JsonlTracker
        # or RecordingTracker to capture the full stream (which also
        # opts the step loop into per-step wall clocks, see run_once).
        self.tracker = tracker if tracker is not None else Tracker()
        # noise is drawn per REQUEST (fold_in of the rid, see _noise), so
        # a request's trajectory is independent of batch composition and
        # admission order — a parked batch's restart and an unpreempted
        # rerun of the same requests produce bitwise-identical latents
        self._noise_key = jax.random.PRNGKey(0)
        self.drift = drift if drift is not None else DriftPolicy()
        self.control = control if control is not None else ControlConfig()
        # instrumentation hook: called as on_step(server, step_index)
        # after every completed sampler step, before the preemption check
        # (tests inject mid-batch arrivals through it)
        self.on_step: Callable[[DiTServer, int], None] | None = None
        if (sampler.pipelined and sp.pp_axis
                and sp.pp_axis in mesh.axis_names and param_axes is not None):
            # stage partitioning: each pipe rank holds its n_layers/pp blocks
            sh = param_shardings(param_axes, cfg, mesh, "serve",
                                 extra_rules={"layers": (sp.pp_axis,)})
            self.params = jax.device_put(params, sh)

        # -- scheduler wiring (DESIGN.md §9) -----------------------------
        dp = self._dp_degree()
        sched = sched if sched is not None else SchedConfig(max_batch=max_batch)
        self.sched_cfg = dataclasses.replace(sched, dp=dp)
        pipe = sampler.pipeline if sampler.pipelined else None
        cfg_deg = (sampler.cfg_degree
                   if (sampler.guided and sampler.cfg_parallel) else 1)
        pp = pipe.pp if pipe else 1
        sp_deg = math.prod(mesh.shape[a] for a in sp.sp_axes)
        # the one plan this mesh/sampler can execute; planned as 1 machine
        # x (cfg*pp*sp) devices — the per-bucket degree of freedom left to
        # the plan cache is the patch count (and the predicted latency the
        # admission policy scores)
        fixed = plan_hybrid(1, cfg_deg * pp * sp_deg, cfg.n_heads,
                            cfg.n_kv_heads, cfg_parallel=cfg_deg > 1,
                            cfg_degree=max(cfg_deg, 2), pp=pp,
                            n_layers=cfg.n_layers)
        self.plan_cache = PlanCache(
            heads=cfg.n_heads, head_dim=cfg.resolved_head_dim,
            kv_heads=cfg.n_kv_heads, n_layers=cfg.n_layers,
            num_steps=sampler.num_steps, guided=sampler.guided,
            guidance_branches=sampler.cfg_degree, dp=dp, net=net,
            candidates=[fixed], base_patches=pipe.patches if pipe else 0,
            tracker=self.tracker)
        forecaster = (ArrivalForecaster(self.control.forecast_alpha,
                                        tracker=self.tracker)
                      if self.control.forecast else None)
        self.scheduler = RequestScheduler(self.plan_cache, self.sched_cfg,
                                          forecaster=forecaster,
                                          tracker=self.tracker)
        self.preempt = self.control.preemption
        self.calibrator = (OnlineCalibrator(self.plan_cache,
                                            self.control.calibration,
                                            tracker=self.tracker)
                           if self.control.calibration is not None else None)

    # -- tracker-backed counters (legacy attribute surface) ---------------
    @property
    def preemptions(self) -> int:
        """Batches parked (not requests)."""
        return int(self.tracker.counter("engine.preemptions"))

    def submit(self, req: DiTRequest) -> None:
        self.scheduler.submit(req, time.time())

    @property
    def pending(self) -> int:
        return self.scheduler.pending

    def _bucket_sampler(self, choice: PlanChoice) -> SamplerConfig:
        """The sampler config for one bucket: the server config with the
        plan cache's per-bucket patch count applied."""
        if not (self.sampler.pipelined and choice.num_patches):
            return self.sampler
        return dataclasses.replace(
            self.sampler, pipeline=dataclasses.replace(
                self.sampler.pipeline, num_patches=choice.num_patches))

    def _step_fn(self, batch: int, seq: int, choice: PlanChoice) -> Callable:
        sc = self._bucket_sampler(choice)

        def build():
            dt = 1.0 / sc.num_steps
            if sc.pipelined:
                def warm(params, x, cond, t, state):
                    return hybrid_sample_step(params, self.cfg, self.ctx, x,
                                              cond, t, dt, sc, state,
                                              warm=True)

                def displaced(params, x, cond, t, state):
                    return hybrid_sample_step(params, self.cfg, self.ctx, x,
                                              cond, t, dt, sc, state,
                                              warm=False)

                # donate the threaded KV state (arg 4): the caller discards
                # the old state each step, so XLA may update it in place
                # instead of allocating a second full-size KV buffer
                return (jax.jit(warm, donate_argnums=(4,)),
                        jax.jit(displaced, donate_argnums=(4,)))

            def f(params, x, cond, t):
                return sample_step(params, self.cfg, self.ctx, x, cond, t,
                                   dt, sc)

            return jax.jit(f)

        # the patch count is part of the compiled step's identity: after
        # an online recalibration changes a bucket's plan choice, the new
        # variant compiles lazily instead of reusing the stale trace
        # the attention the bucket's steps run: displaced attention in a
        # pipelined bucket, else sp_attention's lowering of the joint
        # (text + latent) self-attention
        attn = ("displaced" if sc.pipelined else attention_lowering(
            self.ctx.sp, self.ctx.mesh, self.cfg.text_tokens + seq,
            self.cfg.resolved_head_dim))
        return self.plan_cache.step_fn(batch, seq, build,
                                       variant=choice.num_patches,
                                       build_tags={"attn": attn,
                                                   "block": self.cfg.block})

    def _dp_degree(self) -> int:
        ba = self.ctx.sp.batch_axes or ()
        return math.prod(self.ctx.mesh.shape[a] for a in ba)

    # salt folded into the noise key for dp padding rows (disjoint from
    # request ids, so pad noise is deterministic but never collides)
    _PAD_NOISE_SALT = 1 << 30

    def _noise(self, batch: list[DiTRequest], b: int, t: int) -> jax.Array:
        """Initial latent noise, drawn per ROW from a key that depends
        only on the request's rid (pad rows: the row index) — batch
        composition and admission order cannot change any request's
        trajectory, which is what makes a preempted batch's restart
        bitwise-equal to an unpreempted rerun (DESIGN.md §10)."""
        keys = [jax.random.fold_in(self._noise_key,
                                   batch[i].rid if i < len(batch)
                                   else self._PAD_NOISE_SALT + i)
                for i in range(b)]
        return jnp.stack([
            jax.random.normal(k, (t, LATENT_CHANNELS), self.cfg.dtype)
            for k in keys])

    def _park(self, adm, adm_id: int, step: int) -> None:
        """Preempt the running batch: requests return to the head of
        their bucket with accrued age intact (admission accounting
        reversed); the threaded KV state and partial latents are simply
        dropped (sampler steps leave no other per-batch state — the
        PipeFusion preemption-point argument)."""
        for r in adm.requests:
            r.preemptions += 1
        self.scheduler.requeue(adm.requests, adm.pad_rows)
        self.tracker.count("engine.preemptions")
        # park event: which admission, at which step, whose requests —
        # the restart shows up later as those rids completing under a new
        # admission id with preemptions > 0
        self.tracker.log("engine.park", float(step), step=step,
                         tags={"adm": adm_id, "seq": adm.seq_len,
                               "rids": ",".join(str(r.rid)
                                                for r in adm.requests)})

    def _should_park(self, adm, step: int, num_steps: int,
                     step_times: list[float]) -> bool:
        """The between-steps preemption check (sched/control.py): the
        running batch's remaining time is estimated from its OWN measured
        steps (``sched.control.steady_t_step`` — trace-robust median,
        shared with the calibrator), so the decision self-corrects on
        hardware the analytical model mispredicts.  At the very first
        check the single (possibly trace-paying) sample is used
        deliberately: over-estimating the unknown remaining time errs
        toward the SLA-critical waiting side."""
        if self.preempt is None or step >= num_steps - 1:
            return False
        now = time.time()
        measured = steady_t_step(step_times)
        t_est = measured if measured is not None else adm.plan.t_step
        oldest = min(r.submitted for r in adm.requests)
        victim = self.preempt.should_preempt(
            self.scheduler.waiting_candidates(now),
            remaining_steps=num_steps - 1 - step, t_step=t_est,
            running_age=now - oldest,
            starvation_age=self.sched_cfg.starvation_age,
            running_seq=adm.seq_len, running_k=len(adm.requests),
            max_batch=self.sched_cfg.max_batch)
        return victim is not None

    def run_once(self, flush: bool = True) -> list[DiTResult]:
        """Serve one scheduler admission.  ``flush=False`` lets the
        admission policy defer partial (padded) batches in the hope of
        more arrivals; the default serves whatever scores best now.

        With the control loop engaged (``ControlConfig.preemption`` or
        ``.calibration``) the step loop is measured: each sampler step is
        blocked on and wall-clocked individually, the preemption policy
        runs between steps (a parked batch returns [] and its requests
        re-enter the queue), and completed batches feed the online
        calibrator.  Without it, the loop is the PR-3 sync-free one.

        The call is an ``engine.run_once`` tracker span holding, in order,
        ``engine.admit``, ``engine.prepare``, one ``engine.dispatch`` a
        sampler step, ``engine.sync`` (one a step when measured) and
        ``engine.finish``; the inner ones are tagged with the batch's
        ``rows`` (its requests, padding excluded) and ``seq``.  Under a
        profiler session they sit on the device trace's clock (DESIGN.md
        §12)."""
        with self.tracker.span("engine.run_once"):
            return self._run_admission(flush)

    def _run_admission(self, flush: bool) -> list[DiTResult]:
        """``run_once``'s body: admit, step and finish one batch."""
        tr = self.tracker
        with tr.span("engine.admit"):
            adm = self.scheduler.next_batch(time.time(), flush=flush)
        if adm is None:
            return []
        # admission ordinal: the tag that stitches one batch's step
        # series, park events and request completions together in the
        # metrics stream (DESIGN.md §11)
        adm_id = self.scheduler.admissions
        batch = adm.requests
        n_real = len(batch)
        b = adm.batch_rows  # n_real + dp padding rows (dropped at the end)
        t = adm.seq_len
        # span tags stay low-cardinality: no request or admission ids
        span_tags = {"rows": n_real, "seq": t}
        with tr.span("engine.prepare", tags=span_tags):
            sc = self._bucket_sampler(adm.plan)
            cond = jnp.stack([
                (batch[i].cond if i < n_real and batch[i].cond is not None
                 else jnp.zeros((self.cfg.text_tokens, self.cfg.cond_width),
                                self.cfg.dtype))
                for i in range(b)
            ])
            x = self._noise(batch, b, t)
            fn = self._step_fn(b, t, adm.plan)
        dt = 1.0 / sc.num_steps
        # a persistent sink (JSONL / recording) opts into the per-step
        # series even without the control loop: the wall-clock sync is
        # the price of a trace worth shipping.  Profiling implies
        # measurement — the step spans need the per-step clocks.
        measure = (self.control.engaged or self.tracker.persistent
                   or self.profiler is not None)
        step_tags = {"adm": adm_id, "seq": t, "rows": b}
        step_times: list[float] = []
        drift_vals = []
        resyncs = 0
        t_enqueued = 0.0
        # a step runs one forward a guidance branch of each request
        dispatch_tags = {**span_tags, "branches": sc.branches}

        def dispatch(i: int, f, *args):
            """Enqueue sampler step ``i`` (``f(*args)``) as an
            ``engine.dispatch`` span, tagged also with the guidance
            ``branches``.  Measured, only its profiler annotation is open
            here: ``tick`` publishes the record once the step's clock has
            stopped."""
            nonlocal t_enqueued
            if not measure:
                with tr.span("engine.dispatch", step=i, tags=dispatch_tags):
                    return f(*args)
            with profiler_annotation("engine.dispatch", dispatch_tags):
                out = f(*args)
            t_enqueued = time.perf_counter()
            return out

        def tick(i: int, outputs, t0: float, warm=None) -> bool:
            """Post-step control point: stamp the step's wall clock, run
            the instrumentation hook, then the preemption check.  The
            clock stops at output-ready; span/metric emission happens
            after it (the sampler satellite's contract, applied here
            too)."""
            if measure:
                t_sync = time.perf_counter()
                with profiler_annotation("engine.sync", span_tags):
                    jax.block_until_ready(outputs)
                t_ready = time.perf_counter()
                t_step = t_ready - t0
                step_times.append(t_step)
                ep = tr.epoch
                rec_tags = {**span_tags, "parent": "engine.run_once"}
                tr.span_event("engine.dispatch", t0 - ep, t_enqueued - t0,
                              step=i, tags={**rec_tags,
                                            "branches": sc.branches})
                tr.span_event("engine.sync", t_sync - ep, t_ready - t_sync,
                              step=i, tags=rec_tags)
                self.tracker.log("engine.t_step_s", t_step, step=i,
                                 tags=step_tags)
                if self.profiler is not None:
                    tags = dict(step_tags)
                    tags["pred_t_step_s"] = adm.plan.t_step
                    if "t_compute_step" in adm.plan.pred:
                        # lets trace_report attribute step drift to mfu
                        tags["pred_compute_s"] = adm.plan.pred[
                            "t_compute_step"]
                    if warm is not None:
                        tags["warm"] = bool(warm)
                    self.tracker.span_event(
                        "engine.step", t0 - self.tracker.epoch, t_step,
                        step=i, tags=tags)
            if self.on_step is not None:
                self.on_step(self, i)
            if self._should_park(adm, i, sc.num_steps, step_times):
                self._park(adm, adm_id, i)
                return True
            return False

        parked = False
        prof_ctx = (comm_profile(self.profiler)
                    if self.profiler is not None else contextlib.nullcontext())
        with prof_ctx:
            if sc.pipelined:
                warm_fn, displaced_fn = fn
                pipe = sc.pipeline
                thresholds = [r.drift_threshold for r in batch]
                use_drift = self.drift.engaged(thresholds)
                state = hybrid_state_shape(self.cfg, b, t, sc)
                last_drift: list[float] | None = None
                for i in range(sc.num_steps):
                    if use_drift:
                        warm = self.drift.warm(pipe, i, last_drift,
                                               thresholds,
                                               tracker=self.tracker)
                        if warm and i >= pipe.warmup_steps:
                            resyncs += 1
                            self.tracker.count("engine.resyncs",
                                               tags={"seq": t})
                    else:
                        warm = pipe.warm_step(i)
                    f = warm_fn if warm else displaced_fn
                    t0 = time.perf_counter()
                    x, state, m = dispatch(i, f, self.params, x, cond,
                                           jnp.float32(1.0 - i * dt), state)
                    per = m["kv_drift_per_request"]
                    drift_vals.append(per)
                    if use_drift:
                        # threshold-triggered resync needs the drift on the
                        # host: one device sync per step, only when a bound
                        # is actually configured (DESIGN.md §9)
                        last_drift = [float(per[j]) for j in range(n_real)]
                    if tick(i, (x, state), t0, warm=warm):
                        parked = True
                        break
            else:
                for i in range(sc.num_steps):
                    t0 = time.perf_counter()
                    x = dispatch(i, fn, self.params, x, cond,
                                 jnp.float32(1.0 - i * dt))
                    if tick(i, x, t0):
                        parked = True
                        break
            if not parked and not measure:
                with tr.span("engine.sync", tags=span_tags):
                    x.block_until_ready()
        if self.profiler is not None:
            # pair and publish this admission's device-side leg events
            # (comm.leg / comm.compute / comm.exposed_wait spans)
            emit_leg_spans(self.profiler, self.tracker)
        if parked:
            return []
        with tr.span("engine.finish", tags=span_tags):
            return self._finish(adm, adm_id, sc, x, step_times, drift_vals,
                                resyncs)

    def _finish(self, adm, adm_id: int, sc: SamplerConfig, x: jax.Array,
                step_times: list[float], drift_vals: list,
                resyncs: int) -> list[DiTResult]:
        """Hand each request its row of the finished batch and publish the
        completion telemetry."""
        batch = adm.requests
        n_real = len(batch)
        b, t = adm.batch_rows, adm.seq_len
        now = time.time()
        if self.calibrator is not None and step_times:
            self.calibrator.observe(adm.plan, b, t, step_times)
        # materialise after the timed region; row i is request i's own
        # trajectory (padded rows are never handed to a request)
        drifts = [[float(v[i]) for v in drift_vals] for i in range(n_real)]
        results = [
            DiTResult(r.rid, x[i], now - r.submitted, sc.num_steps,
                      kv_drift=drifts[i] if drift_vals else [],
                      resyncs=resyncs,
                      sla_met=(r.sla is None
                               or now <= r.submitted + r.sla),
                      step_times=list(step_times),
                      preemptions=r.preemptions)
            for i, r in enumerate(batch)
        ]
        # completion telemetry — emitted outside the timed region.  The
        # kv_drift series is logged here (not mid-loop) so the stream
        # carries it without adding any per-step host sync.
        tr = self.tracker
        tr.log("engine.batch_done", float(n_real),
               tags={"adm": adm_id, "seq": t, "rows": b})
        if drift_vals and n_real:
            for s in range(len(drift_vals)):
                mean = sum(drifts[i][s] for i in range(n_real)) / n_real
                tr.log("engine.kv_drift", mean, step=s,
                       tags={"adm": adm_id, "seq": t})
        for r, req in zip(results, batch):
            tr.count("engine.completed", tags={"seq": t})
            if r.preemptions:
                tr.count("engine.restarted_requests")
            tr.log("engine.request_done", r.latency,
                   tags={"adm": adm_id, "rid": r.rid, "seq": t,
                         "preemptions": r.preemptions,
                         "sla_met": r.sla_met})
            if req.sla is not None:
                tr.count("engine.sla_met" if r.sla_met
                         else "engine.sla_miss", tags={"seq": t})
        return results

    def serve(self) -> list[DiTResult]:
        """Drain the queue.  With the arrival forecaster engaged
        (``ControlConfig.forecast``), each round first offers the
        admission policy a non-flush pick so the §10 deferral horizon is
        consulted — a padded candidate whose missing rows are forecast
        to arrive within its slack keeps waiting for them (only
        meaningful with dp > 1: the deferral applies to dp-padded
        batches).  A round that admits nothing and parks nothing falls
        back to a flush pick, so the drain always terminates."""
        out = []
        while self.scheduler.pending:
            if self.scheduler.forecaster is not None:
                pre = self.preemptions
                got = self.run_once(flush=False)
                if got or self.preemptions != pre:
                    out.extend(got)
                    continue
            out.extend(self.run_once(flush=True))
        return out


# ---------------------------------------------------------------------------
# AR decode serving (assigned LM archs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ARRequest:
    rid: int
    prompt: jax.Array  # [L_prompt] int32
    max_new_tokens: int = 16
    priority: float = 0.0  # higher admits sooner; aging bounds starvation
    submitted: int = 0  # engine tick at submission (stamped by submit())


@dataclasses.dataclass
class Slot:
    req: ARRequest | None = None
    pos: int = 0  # next cache index to write
    generated: list[int] = dataclasses.field(default_factory=list)


class ARServer:
    """Fixed-slot continuous batching over a sequence-sharded KV cache.

    Prefill is implemented as teacher-forced decode of the prompt (one
    engine, one cache layout — adequate for the assigned decode shapes;
    a chunked-prefill path is a straightforward extension).

    Freed slots are filled by effective priority ``priority + age *
    aging_rate`` (serving/sched ``aged_priority``) rather than raw FIFO:
    a high-priority stream can jump the queue, but every waiting request's
    effective priority grows with its queue age, so a request of base
    priority p is admitted within ``(p_max - p) / aging_rate`` ticks of
    any fresher competitor — the same starvation bound the DiT scheduler
    enforces on buckets.  Ties (equal effective priority, e.g. all base 0)
    reduce to FIFO.
    """

    def __init__(self, params, cfg: ModelConfig, mesh, sp: SPConfig,
                 batch_slots: int = 4, max_len: int = 256,
                 cache_dtype=jnp.float32, aging_rate: float = 0.1,
                 tracker: Tracker | None = None):
        self.params = params
        self.cfg = cfg
        self.ctx = ParallelContext(mesh, sp, "decode")
        self.bundle = get_model(cfg)
        self.slots = [Slot() for _ in range(batch_slots)]
        self.max_len = max_len
        self.aging_rate = aging_rate
        self.caches = self.bundle.init_caches(cfg, batch_slots, max_len, cache_dtype)
        self.queue: deque[ARRequest] = deque()
        self.results: dict[int, list[int]] = {}
        self._ticks = 0
        # metrics sink (DESIGN.md §11): slot admission / completion
        # counters plus the queue-wait series, same schema as DiTServer
        self.tracker = tracker if tracker is not None else Tracker()

        def step(params, caches, tokens, cur_index):
            batch = {"tokens": tokens}
            logits, caches = self.bundle.step(params, batch, caches,
                                              cur_index, cfg, self.ctx)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), caches

        self._step = jax.jit(step)

    def submit(self, req: ARRequest) -> None:
        req.submitted = self._ticks
        self.queue.append(req)
        self.tracker.count("ar.submitted")

    def _take_next(self) -> ARRequest:
        """Pop the waiting request with the highest aged priority (stable:
        FIFO among equals — max() keeps the first of tied keys)."""
        best = max(self.queue,
                   key=lambda r: aged_priority(r.priority,
                                               self._ticks - r.submitted,
                                               self.aging_rate))
        self.queue.remove(best)
        return best

    def _admit(self) -> None:
        for s in self.slots:
            if s.req is None and self.queue:
                s.req = self._take_next()
                s.pos = 0
                s.generated = []
                self.tracker.count("ar.admitted")
                self.tracker.log("ar.queue_wait_ticks",
                                 float(self._ticks - s.req.submitted),
                                 tags={"rid": s.req.rid})

    def tick(self) -> None:
        """Advance every active slot one position.

        All slots share one cur_index per tick in this reference engine;
        requests are aligned at admission (pos 0).  Slots therefore run in
        lockstep — the standard static-batching baseline."""
        self._admit()
        self._ticks += 1
        active = [s for s in self.slots if s.req is not None]
        if not active:
            return
        self.tracker.count("ar.ticks")
        pos = active[0].pos
        tokens = []
        for s in self.slots:
            if s.req is None:
                tokens.append(0)
            elif s.pos < len(s.req.prompt):
                tokens.append(int(s.req.prompt[s.pos]))
            else:
                tokens.append(s.generated[-1] if s.generated else 0)
        tok = jnp.asarray(tokens, jnp.int32)[:, None]
        nxt, self.caches = self._step(self.params, self.caches, tok,
                                      jnp.int32(pos))
        for i, s in enumerate(self.slots):
            if s.req is None:
                continue
            s.pos += 1
            if s.pos >= len(s.req.prompt):
                s.generated.append(int(nxt[i]))
            if (len(s.generated) >= s.req.max_new_tokens
                    or s.pos >= self.max_len - 1):
                self.results[s.req.rid] = list(s.generated)
                self.tracker.count("ar.completed")
                self.tracker.log("ar.request_done", float(len(s.generated)),
                                 tags={"rid": s.req.rid})
                s.req = None

    def serve(self, max_ticks: int = 10_000) -> dict[int, list[int]]:
        t = 0
        while (self.queue or any(s.req for s in self.slots)) and t < max_ticks:
            self.tick()
            t += 1
        return self.results
