"""Adaptive serving control loop (serving/sched/{control,forecast}.py,
DESIGN.md §10): arrival forecasting, the slack-aware deferral horizon,
and the preemption invariants (ISSUE 5) —

  (a) a preempted request never loses accrued starvation age,
  (b) the PR-3 hard starvation bound survives adversarial arrival
      streams with preemption enabled,
  (c) preemption never fires when the waiting side's remaining slack
      covers the running batch.

All host-side: the property tests drive the same scheduler objects and
step-granular simulation the engine and the replay harness use, on
simulated time (hypothesis, no wall clock)."""
import dataclasses
import random

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving.sched import (
    ArrivalForecaster,
    Candidate,
    ControlConfig,
    PreemptionPolicy,
    RequestScheduler,
    SchedConfig,
)
from tests.test_sched import Req, make_cache


def make_sched(forecaster=None, **kw):
    cfg = SchedConfig(max_batch=4, dp=2, starvation_age=10.0,
                      aging_rate=1.0, default_slack=100.0, defer_slack=1.0)
    cfg = dataclasses.replace(cfg, **kw)
    return RequestScheduler(make_cache(dp=cfg.dp), cfg,
                            forecaster=forecaster)


def cand(min_slack: float, age: float = 0.0) -> Candidate:
    """A candidate carrying only what should_preempt reads."""
    return Candidate(bucket=None, k=1, batch_rows=2, pad_rows=1, plan=None,
                     min_slack=min_slack, age=age, score=min_slack)


# ---------------------------------------------------------------------------
# arrival forecaster
# ---------------------------------------------------------------------------

def test_forecaster_needs_two_arrivals():
    f = ArrivalForecaster()
    assert f.expected_fill_time(256, 1, now=0.0) is None
    f.observe(256, 0.0)
    assert f.expected_fill_time(256, 1, now=0.5) is None
    assert f.rate(256) == 0.0
    f.observe(256, 2.0)
    assert f.expected_fill_time(256, 1, now=2.0) is not None
    assert f.rate(256) == pytest.approx(0.5)


def test_forecaster_tracks_steady_rate():
    f = ArrivalForecaster(alpha=0.5)
    for i in range(20):
        f.observe(512, i * 0.1)
    assert f.rate(512) == pytest.approx(10.0, rel=0.01)
    # k more arrivals ≈ k·gap; the elapsed time since the last arrival is
    # credited against the first gap
    assert f.expected_fill_time(512, 3, now=1.9) == pytest.approx(
        0.3, abs=0.05)
    assert f.expected_fill_time(512, 3, now=1.95) == pytest.approx(
        0.25, abs=0.05)
    # a bucket never seen has no estimate
    assert f.expected_fill_time(1024, 1, now=2.0) is None


@given(st.integers(1, 6), st.integers(0, 10_000))
@settings(max_examples=30, deadline=None)
def test_forecaster_fill_time_monotone_in_k(k, seed):
    rnd = random.Random(seed)
    f = ArrivalForecaster()
    t = 0.0
    for _ in range(rnd.randint(2, 30)):
        t += rnd.uniform(0.01, 1.0)
        f.observe(256, t)
    a = f.expected_fill_time(256, k, now=t)
    b = f.expected_fill_time(256, k + 1, now=t)
    assert a is not None and b is not None and 0.0 <= a <= b


# ---------------------------------------------------------------------------
# slack-aware deferral horizon (admission + forecaster)
# ---------------------------------------------------------------------------

def test_dried_up_bucket_served_padded_instead_of_stalling():
    """PR-3 defers a padded batch until flush whenever slack allows; with
    the forecaster, a bucket whose arrivals are too slow to fill the pad
    within the slack is served immediately (DESIGN.md §10)."""
    hist = [Req(0, 256), Req(1, 256)]
    fore = ArrivalForecaster()
    old, new = make_sched(), make_sched(forecaster=fore)
    for s in (old, new):
        for i, r in enumerate(hist):
            s.submit(dataclasses.replace(r), now=60.0 * i)  # 60 s gaps
        s.next_batch(120.0, flush=True)  # drain history (k=2, no pad)
        s.submit(Req(2, 256, sla=20.0), now=120.0)
    # the lone request needs 1 pad row; its ~59 s forecast fill time does
    # NOT fit the 20 s slack, so the forecaster admits it padded now
    assert old.next_batch(121.0, flush=False) is None  # PR-3: stalls
    adm = new.next_batch(121.0, flush=False)
    assert adm is not None and adm.pad_rows == 1 and len(adm.requests) == 1


def test_fast_bucket_still_defers_for_packing():
    """When arrivals ARE fast enough to fill the pad inside the slack the
    forecaster keeps deferring — same packing win as PR-3."""
    fore = ArrivalForecaster()
    s = make_sched(forecaster=fore)
    for i in range(4):  # 10 ms interarrival history
        s.submit(Req(i, 256), now=0.01 * i)
    s.next_batch(0.04, flush=True)
    s.submit(Req(4, 256), now=0.05)  # lone request, slack = default 100 s
    assert s.next_batch(0.051, flush=False) is None  # fill ≈ 10 ms: wait
    adm = s.next_batch(0.06, flush=True)
    assert adm is not None


def test_forecaster_evicts_idle_buckets():
    """With ``idle_age`` set, a bucket whose arrivals dried up is dropped
    on the next observe — the per-seq_len map stays bounded by the set of
    RECENTLY seen resolutions, not every resolution ever seen (ISSUE 9:
    ``buckets`` grew without bound)."""
    f = ArrivalForecaster(idle_age=1.0)
    f.observe(1024, 0.0)
    f.observe(256, 0.5)
    for i in range(6):
        f.observe(256, 0.6 + 0.1 * i)
    assert 1024 not in f.buckets  # idle > 1 s: evicted by a 256 observe
    assert set(f.buckets) == {256}
    # a returning bucket re-seeds from scratch (needs two arrivals again)
    f.observe(1024, 1.2)
    assert f.rate(1024) == 0.0


def test_forecaster_eviction_bounds_memory_under_resolution_churn():
    f = ArrivalForecaster(idle_age=0.5)
    for i in range(500):  # adversarial: every request a new resolution
        f.observe(256 + i, 0.1 * i)
    assert len(f.buckets) <= 6  # only buckets inside the idle window
    # the PR-5 default (no idle_age) keeps the old retain-forever shape
    g = ArrivalForecaster()
    for i in range(100):
        g.observe(256 + i, 0.1 * i)
    assert len(g.buckets) == 100


def test_forecaster_evict_idle_direct_call_counts_evictions():
    """Long-idle owners (the fleet tier) call ``evict_idle`` directly;
    eviction uses caller time only and is published as a counter."""
    from repro.serving.sched import RecordingTracker

    trk = RecordingTracker()
    f = ArrivalForecaster(idle_age=2.0, tracker=trk)
    f.observe(256, 0.0)
    f.observe(512, 1.0)
    assert f.evict_idle(1.5) == 0  # nothing idle yet
    assert f.evict_idle(2.5) == 1  # 256 idle 2.5 s > 2 s
    assert set(f.buckets) == {512}
    assert trk.counter("forecast.evictions", {"seq": 256}) == 1
    with pytest.raises(AssertionError):
        ArrivalForecaster(idle_age=0.0)


# ---------------------------------------------------------------------------
# (a) preemption preserves accrued age and FIFO position
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000), st.integers(1, 4))
@settings(max_examples=25, deadline=None)
def test_requeue_preserves_age_and_order(seed, dp):
    rnd = random.Random(seed)
    s = make_sched(dp=dp)
    reqs = []
    t = 0.0
    for i in range(rnd.randint(2, 12)):
        t += rnd.uniform(0.0, 2.0)
        r = Req(i, rnd.choice([256, 512]))
        reqs.append(r)
        s.submit(r, now=t)
    now = t + rnd.uniform(0.0, 5.0)
    adm = s.next_batch(now, flush=True)
    submitted = {r.rid: r.submitted for r in adm.requests}
    ages_before = {r.rid: now - r.submitted for r in adm.requests}
    s.requeue(adm.requests)
    # accrued age intact: submitted stamps are untouched by the park
    later = now + 1.0
    adm2 = s.next_batch(later, flush=True)
    assert adm2.seq_len == adm.seq_len
    assert [r.rid for r in adm2.requests][:len(adm.requests)] == [
        r.rid for r in adm.requests]  # FIFO position restored (head)
    for r in adm2.requests:
        if r.rid in submitted:
            assert r.submitted == submitted[r.rid]
            assert later - r.submitted == pytest.approx(
                ages_before[r.rid] + 1.0)


# ---------------------------------------------------------------------------
# (c) the decision rule never fires when slack covers the running batch
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000), st.integers(2, 30))
@settings(max_examples=50, deadline=None)
def test_no_preemption_when_slack_covers_running_batch(seed, remaining):
    rnd = random.Random(seed)
    pol = PreemptionPolicy(margin=rnd.choice([0.0, 0.01]))
    t_step = rnd.uniform(1e-4, 0.1)
    t_rem = remaining * t_step
    covered = [cand(t_rem + rnd.uniform(0.0, 10.0) + pol.margin)
               for _ in range(rnd.randint(1, 5))]
    assert pol.should_preempt(covered, remaining_steps=remaining,
                              t_step=t_step, running_age=0.0,
                              starvation_age=10.0) is None


def test_preemption_fires_only_for_salvageable_critical_candidates():
    pol = PreemptionPolicy(min_remaining_steps=2)
    kw = dict(remaining_steps=10, t_step=0.01, running_age=0.0,
              starvation_age=10.0)
    # doomed (negative slack): parking cannot save it
    assert pol.should_preempt([cand(-0.01)], **kw) is None
    # salvageable and doomed-by-waiting: fires, tightest slack wins
    got = pol.should_preempt([cand(0.05), cand(0.02)], **kw)
    assert got is not None and got.min_slack == 0.02
    # nearly-finished batches are never parked
    assert pol.should_preempt([cand(0.02)], remaining_steps=1, t_step=0.01,
                              running_age=0.0, starvation_age=10.0) is None
    # an overdue running batch is immune (carries the starvation bound)
    assert pol.should_preempt([cand(0.02)], remaining_steps=10, t_step=0.01,
                              running_age=10.0, starvation_age=10.0) is None


def test_same_bucket_candidate_only_useful_if_it_fits_the_restart():
    """Parking for the running batch's OWN bucket is futile unless the
    parked requests and the triggering ones fit one batch — the parked
    batch re-enters at the head, so otherwise the re-admission re-serves
    it and the trigger re-fires (park/restart thrash)."""
    from repro.serving.sched import Bucket

    c = Candidate(bucket=Bucket(256), k=1, batch_rows=1, pad_rows=0,
                  plan=None, min_slack=0.02, age=0.0, score=0.0)
    kw = dict(remaining_steps=10, t_step=0.01, running_age=0.0,
              starvation_age=10.0)
    pol = PreemptionPolicy(min_remaining_steps=2)
    # legacy callers without running-batch info keep the plain rule
    assert pol.should_preempt([c], **kw) is not None
    # same bucket, parked 4 + trigger 1 > max_batch 4: futile, skip
    assert pol.should_preempt([c], running_seq=256, running_k=4,
                              max_batch=4, **kw) is None
    # fits one batch with the parked requests: regrouping serves it
    assert pol.should_preempt([c], running_seq=256, running_k=3,
                              max_batch=4, **kw) is not None
    # a different bucket is unaffected by the futility rule
    assert pol.should_preempt([c], running_seq=512, running_k=4,
                              max_batch=4, **kw) is not None


def test_control_config_engaged():
    assert not ControlConfig().engaged
    assert ControlConfig(preemption=PreemptionPolicy()).engaged
    from repro.serving.sched import CalibrationConfig
    assert ControlConfig(calibration=CalibrationConfig()).engaged


# ---------------------------------------------------------------------------
# (b) hard starvation bound under adversarial streams with preemption
# ---------------------------------------------------------------------------

def test_requeue_reverses_admission_accounting():
    """A parked batch must not double-count in BucketStats: pop's
    accounting is reversed on requeue and re-applied on re-admission, so
    totals() reflects completed batches only."""
    s = make_sched(dp=2)
    s.submit(Req(0, 256), now=0.0)
    adm = s.next_batch(1.0, flush=True)  # k=1, 1 pad row
    assert adm.pad_rows == 1
    s.requeue(adm.requests, adm.pad_rows)
    t = s.totals()
    assert (t.admitted, t.batches, t.padded_rows, t.padded_token_work,
            t.real_token_work) == (0, 0, 0, 0, 0)
    s.next_batch(2.0, flush=True)  # re-admission re-accounts exactly once
    t = s.totals()
    assert t.admitted == 1 and t.batches == 1 and t.padded_rows == 1
    assert t.padded_token_work == t.real_token_work == 256
    assert t.max_wait >= 1.0  # the first admission's wait is kept


def test_sampler_interrupt_stops_between_steps():
    """sample(interrupt=...) — the step-granular park hook for callers
    that drive the sampler directly rather than through DiTServer."""
    import dataclasses as dc

    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.serving import SamplerConfig, sample

    cfg = dc.replace(get_reduced("flux-12b"), dtype="float32")
    calls = []

    def step_fn(x, cond, t):
        calls.append(float(t))
        return x + 1.0

    metrics = []
    import jax

    out = sample(None, cfg, None, key=jax.random.PRNGKey(0), batch=1,
                 seq_len=8, cond=jnp.zeros((1, 4, 8)),
                 sc=SamplerConfig(num_steps=5), step_fn=step_fn,
                 metrics=metrics, interrupt=lambda i: i == 1)
    assert len(calls) == 2  # stopped after step 1, before step 2
    assert len(metrics) == 2 and all(m["t_step_s"] > 0 for m in metrics)
    noise = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 64), cfg.dtype)
    # latents as of the parked step: two +1 steps applied, not five
    assert bool(jnp.allclose(out, noise + 2.0, atol=1e-6))


# ---------------------------------------------------------------------------
# engine integration: park + restart + online recalibration (1 device)
# ---------------------------------------------------------------------------

def test_engine_parks_restarts_and_recalibrates(mesh1):
    """A real (tiny) DiTServer with the full control loop: an urgent
    request injected mid-batch parks the running batch (accrued age
    kept, request completes later), per-step wall clocks are surfaced,
    and the online calibrator — fed CPU step times that are orders of
    magnitude off the analytical µs predictions — refits and invalidates
    the plan cache's scores."""
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from repro.configs import get_reduced
    from repro.core import SPConfig as SP_
    from repro.models import get_model
    from repro.serving import (
        CalibrationConfig,
        DiTRequest,
        DiTServer,
        SamplerConfig,
    )

    cfg = dc.replace(get_reduced("flux-12b"), dtype="float32")
    bundle = get_model(cfg)
    params, _ = bundle.init(cfg, jax.random.PRNGKey(0), 1)
    sp = SP_(strategy="full", sp_axes=("model",), batch_axes=("data",))
    srv = DiTServer(
        params, cfg, mesh1, sp, sampler=SamplerConfig(num_steps=3),
        max_batch=4,
        sched=SchedConfig(max_batch=4, starvation_age=3600.0,
                          default_slack=1e9),
        control=ControlConfig(
            preemption=PreemptionPolicy(min_remaining_steps=1),
            calibration=CalibrationConfig(min_samples=2, refit_every=2),
            forecast=True))
    srv.submit(DiTRequest(rid=0, seq_len=32))
    srv.submit(DiTRequest(rid=1, seq_len=32))
    injected = []

    def inject(server, step):
        if not injected:
            injected.append(step)
            server.submit(DiTRequest(rid=2, seq_len=64, sla=0.5))

    srv.on_step = inject
    results = srv.serve()
    assert sorted(r.rid for r in results) == [0, 1, 2]
    by_rid = {r.rid: r for r in results}
    # the 32 batch was parked for the urgent 64 (first CPU step includes
    # its jit trace: far above the 0.5 s slack), then restarted clean
    assert srv.preemptions >= 1
    assert by_rid[0].preemptions >= 1 and by_rid[1].preemptions >= 1
    assert by_rid[2].preemptions == 0
    for r in results:
        assert len(r.step_times) == 3  # step-granular wall clocks
        assert all(t > 0.0 for t in r.step_times)
        assert bool(jnp.all(jnp.isfinite(r.latents)))
    # online recalibration: measured CPU seconds vs predicted µs is far
    # past any drift threshold — scores invalidated, steps not retraced
    assert srv.calibrator.refits >= 1
    assert srv.calibrator.recalibrations >= 1
    assert srv.plan_cache.invalidations == srv.calibrator.recalibrations
    assert srv.plan_cache.traces == len(srv.plan_cache._steps)
    # forecast engaged: serve() drove the non-flush deferral path and
    # the forecaster saw every bucket's arrivals
    assert srv.scheduler.forecaster is not None
    assert set(srv.scheduler.forecaster.buckets) == {32, 64}


@given(st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_starvation_bound_survives_preemption(seed):
    """Adversarial seeded streams (steady tight-SLA bursts trying to
    preempt everything) through the step-granular simulation: every
    request is served and no wait exceeds the PR-3 bound plus the
    batches already in flight (overdue batches are preemption-immune, so
    ages cannot grow unboundedly)."""
    from benchmarks.sched_sweep import (
        BucketedPolicy,
        SimRequest,
        STARVATION_AGE,
        simulate,
    )

    rnd = random.Random(seed)
    reqs, t, rid = [], 0.0, 0
    for _ in range(rnd.randint(20, 60)):
        t += rnd.uniform(0.0005, 0.02)
        if rnd.random() < 0.5:  # adversary: tight-SLA short request
            reqs.append(SimRequest(rid=rid, seq_len=256, arrival=round(t, 6),
                                   sla=rnd.uniform(0.005, 0.02)))
        else:  # victim tier: long best-effort / loose-SLA request
            reqs.append(SimRequest(
                rid=rid, seq_len=rnd.choice([512, 1024]),
                arrival=round(t, 6),
                sla=None if rnd.random() < 0.5 else rnd.uniform(0.5, 2.0)))
        rid += 1
    stats = simulate(BucketedPolicy(), [dataclasses.replace(r) for r in reqs],
                     preempt=PreemptionPolicy())
    assert stats["served"] == len(reqs)
    bound = STARVATION_AGE + 4 * stats["max_batch_s"]
    assert stats["max_wait"] <= bound, (stats["max_wait"], bound)
