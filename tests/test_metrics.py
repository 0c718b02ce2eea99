"""Serving observability subsystem (serving/metrics.py, DESIGN.md §11):
the tracker sink contract the whole control loop now publishes through —

  (a) counters are monotone and every counter record carries the NEW
      cumulative total (a trace replays without summing),
  (b) a ``JsonlTracker`` trace round-trips bit-exactly (bytes and
      ``Record`` objects) through ``read_jsonl``,
  (c) stream order (``seq``), ``step`` and ``tags`` survive the disk
      round-trip unchanged,
  (d) ``NullTracker`` is a TRUE no-op,
  (e) every record is schema-versioned and ``validate_record`` rejects
      each class of malformed record,

plus the counter-migration regression: the legacy attribute surface
(``PlanCache.hits`` & co.) must read exactly what the record stream says
on a mixed-resolution serve — pinned here so future sinks can't drift
from the attributes tests and launchers consume.

All host-side (no mesh; jax only to read a profiler trace); property
tests use hypothesis."""
import dataclasses
import json
import pathlib
import random
import tempfile

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving.metrics import (
    KINDS,
    SCHEMA_VERSION,
    JsonlTracker,
    NullTracker,
    Record,
    RecordingTracker,
    SeriesStats,
    Tracker,
    read_jsonl,
    replay,
    validate_record,
)

NAMES = ("engine.t_step_s", "plan_cache.step_hit", "sched.admissions",
         "calibration.drift_ratio", "sim.batches")
TAGSETS = (None, {"seq": 256}, {"seq": 512, "rows": 4},
           {"adm": 3, "warm": True}, {"param": "alpha_us"})


def _drive(tracker: Tracker, seed: int, n_ops: int = 40) -> None:
    """Deterministic mixed counter/gauge stream (the shared generator the
    property tests replay into multiple sinks)."""
    rnd = random.Random(seed)
    for i in range(n_ops):
        name = rnd.choice(NAMES)
        tags = rnd.choice(TAGSETS)
        step = rnd.randrange(100) if rnd.random() < 0.5 else None
        if rnd.random() < 0.5:
            tracker.count(name, rnd.randrange(0, 5), step=step, tags=tags)
        else:
            tracker.log(name, rnd.uniform(-10, 10), step=step, tags=tags)


# ---------------------------------------------------------------------------
# (a) counter semantics
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000))
@settings(max_examples=50, deadline=None)
def test_counters_monotone_and_records_carry_totals(seed):
    rnd = random.Random(seed)
    t = RecordingTracker()
    expect: dict[tuple, float] = {}
    for _ in range(rnd.randint(1, 60)):
        name = rnd.choice(NAMES)
        tags = rnd.choice(TAGSETS)
        inc = rnd.randrange(0, 7)
        key = (name, tuple(sorted((tags or {}).items())))
        expect[key] = expect.get(key, 0.0) + inc
        total = t.count(name, inc, tags=tags)
        # count() returns (and the record carries) the NEW cumulative total
        assert total == expect[key]
        assert t.records[-1].kind == "counter"
        assert t.records[-1].value == expect[key]
        assert t.counter(name, tags) == expect[key]
    # per-series record values never decrease (monotone counters)
    per_series: dict[tuple, list[float]] = {}
    for r in t.records:
        per_series.setdefault(
            (r.name, tuple(sorted(r.tags.items()))), []).append(r.value)
    for vals in per_series.values():
        assert vals == sorted(vals)
    # counter_total sums across every tag set of the name
    for name in NAMES:
        assert t.counter_total(name) == pytest.approx(
            sum(v for (n, _), v in expect.items() if n == name))


def test_negative_counter_increment_rejected():
    with pytest.raises(AssertionError):
        Tracker().count("x", -1.0)


def test_gauge_series_stats():
    t = Tracker()
    for v in (3.0, -1.0, 5.0):
        t.log("g", v, tags={"seq": 256})
    st_ = t.series("g", {"seq": 256})
    assert (st_.n, st_.vmin, st_.vmax, st_.last) == (3, -1.0, 5.0, 5.0)
    assert st_.mean == pytest.approx(7.0 / 3.0)
    # an unseen series reads as empty stats, not KeyError
    empty = t.series("g", {"seq": 1024})
    assert isinstance(empty, SeriesStats) and empty.n == 0


# ---------------------------------------------------------------------------
# (b) JSONL bit-exact round-trip
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_jsonl_round_trip_bit_exact(seed):
    with tempfile.TemporaryDirectory() as td:
        p1 = pathlib.Path(td) / "a.jsonl"
        p2 = pathlib.Path(td) / "b.jsonl"
        rec = RecordingTracker()
        with JsonlTracker(p1) as j1:
            _drive(rec, seed)
            _drive(j1, seed)
        # Record-level equality: disk stream == in-memory stream
        assert read_jsonl(p1) == rec.records
        # byte-level determinism: the same stream writes identical bytes
        with JsonlTracker(p2) as j2:
            _drive(j2, seed)
        assert p1.read_bytes() == p2.read_bytes()
        # aggregate parity: both sinks saw the same totals
        for name in NAMES:
            assert j1.counter_total(name) == rec.counter_total(name)


def test_jsonl_valid_at_every_prefix(tmp_path):
    """Every line is complete JSON the moment it's written — a crashed
    run's trace is readable up to the last record."""
    p = tmp_path / "t.jsonl"
    t = JsonlTracker(p)
    t.count("a", 1)
    t.log("b", 2.5, step=3, tags={"seq": 256})
    t.flush()
    lines = p.read_text().splitlines()
    assert len(lines) == 2
    for line in lines:
        assert validate_record(json.loads(line)) == []
    t.close()
    t.close()  # idempotent


def test_replay_rebuilds_aggregates(tmp_path):
    p = tmp_path / "t.jsonl"
    with JsonlTracker(p) as t:
        _drive(t, seed=7)
    back = replay(read_jsonl(p))
    for name in NAMES:
        assert back.counter_total(name) == t.counter_total(name)
    for tags in TAGSETS:
        for name in NAMES:
            assert back.counter(name, tags) == t.counter(name, tags)
            assert back.series(name, tags).n == t.series(name, tags).n


# ---------------------------------------------------------------------------
# (c) ordering, step and tags survive the round-trip
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_seq_total_order_and_step_tags_preserved(seed):
    with tempfile.TemporaryDirectory() as td:
        p = pathlib.Path(td) / "t.jsonl"
        with JsonlTracker(p) as t:
            _drive(t, seed)
        recs = read_jsonl(p)
        # seq is the dense 0..n-1 total order of the stream, in file order
        assert [r.seq for r in recs] == list(range(len(recs)))
        # regenerate the identical stream and compare field-by-field
        mirror = RecordingTracker()
        _drive(mirror, seed)
        for a, b in zip(recs, mirror.records):
            assert (a.name, a.kind, a.value, a.step, a.tags) == \
                   (b.name, b.kind, b.value, b.step, b.tags)


def test_tag_order_is_canonical():
    """The same tag set in any insertion order is one series."""
    t = Tracker()
    t.count("c", 1, tags={"a": 1, "b": 2})
    t.count("c", 1, tags={"b": 2, "a": 1})
    assert t.counter("c", {"a": 1, "b": 2}) == 2
    assert t.counter_total("c") == 2


# ---------------------------------------------------------------------------
# (d) NullTracker is a TRUE no-op
# ---------------------------------------------------------------------------

def test_null_tracker_noop():
    t = NullTracker()
    assert t.count("a", 5, tags={"seq": 256}) == 0.0
    t.log("b", 1.0, step=3)
    assert t.counter("a", {"seq": 256}) == 0.0
    assert t.counter_total("a") == 0.0
    assert t.series("b").n == 0
    assert t.summary() == []
    assert not t.persistent


# ---------------------------------------------------------------------------
# (e) schema versioning + validate_record
# ---------------------------------------------------------------------------

def test_every_record_is_schema_versioned():
    t = RecordingTracker()
    _drive(t, seed=3)
    assert t.records, "generator produced no records"
    for r in t.records:
        assert r.schema == SCHEMA_VERSION
        assert r.kind in KINDS
        assert validate_record(r.to_dict()) == []


def test_record_dict_round_trip():
    r = Record(name="n", value=1.5, kind="gauge", step=4,
               tags={"seq": 256, "warm": True}, seq=9)
    assert Record.from_dict(r.to_dict()) == r
    # omitted optionals stay omitted on disk but default on the way back
    bare = Record(name="n", value=2.0, kind="counter", seq=0)
    d = bare.to_dict()
    assert "step" not in d and "tags" not in d
    assert Record.from_dict(d) == bare


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.pop("schema"), "missing field"),
    (lambda d: d.pop("name"), "missing field"),
    (lambda d: d.pop("seq"), "missing field"),
    (lambda d: d.update(schema="metrics.v0"), "schema"),
    (lambda d: d.update(kind="histogram"), "kind"),
    (lambda d: d.update(value=True), "not a number"),
    (lambda d: d.update(value="fast"), "not a number"),
    (lambda d: d.update(seq=-1), "seq"),
    (lambda d: d.update(step=1.5), "step"),
    (lambda d: d.update(tags={"k": [1, 2]}), "tag"),
    (lambda d: d.update(surprise=1), "unknown fields"),
])
def test_validate_record_rejects_malformed(mutate, needle):
    d = Record(name="n", value=1.0, kind="gauge", seq=0).to_dict()
    mutate(d)
    errs = validate_record(d)
    assert errs and any(needle in e for e in errs), errs


def test_read_jsonl_raises_on_malformed_line(tmp_path):
    p = tmp_path / "bad.jsonl"
    good = Record(name="n", value=1.0, kind="gauge", seq=0).to_dict()
    bad = dict(good, schema="metrics.v0")
    p.write_text(json.dumps(good) + "\n" + json.dumps(bad) + "\n")
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        read_jsonl(p)
    assert len(read_jsonl(p, validate=False)) == 2


# ---------------------------------------------------------------------------
# summary table
# ---------------------------------------------------------------------------

def test_summary_rows_and_format():
    t = Tracker()
    t.count("c", 2, tags={"seq": 256})
    t.log("g", 1.5)
    t.log("g", 2.5)
    rows = {(r["name"], r["kind"]): r for r in t.summary()}
    assert rows[("c", "counter")]["value"] == 2
    g = rows[("g", "gauge")]
    assert (g["n"], g["mean"], g["min"], g["max"]) == (2, 2.0, 1.5, 2.5)
    text = t.format_summary()
    assert "c{seq=256}" in text and "counter" in text and "gauge" in text


# ---------------------------------------------------------------------------
# counter-migration regression: legacy attributes == the record stream
# ---------------------------------------------------------------------------

def _mixed_drain(tracker: Tracker):
    """A mixed-resolution stream through the real scheduler + plan cache
    (the objects the engine wires to one tracker), drained to empty."""
    from repro.serving.sched import RequestScheduler, SchedConfig
    from tests.test_sched import Req, make_cache

    cache = make_cache(dp=2, tracker=tracker)
    sched = RequestScheduler(
        cache, SchedConfig(max_batch=4, dp=2, starvation_age=10.0,
                           aging_rate=1.0, default_slack=100.0,
                           defer_slack=1.0), tracker=tracker)
    lens = [256, 512, 256, 1024, 512, 256, 1024, 256, 256, 512]
    for i, n in enumerate(lens):
        sched.submit(Req(i, n), now=0.01 * i)
    admissions = []
    now = 1.0
    while sched.pending:
        adm = sched.next_batch(now, flush=True)
        cache.step_fn(adm.batch_rows, adm.seq_len, lambda: (lambda: None))
        admissions.append(adm)
        now += 0.1
    return cache, sched, admissions


def test_legacy_attributes_match_record_stream():
    t = RecordingTracker()
    cache, sched, admissions = _mixed_drain(t)

    def final_totals(name: str) -> float:
        # counter records carry cumulative totals: the last record per
        # tag set is that series' final count
        last: dict[tuple, float] = {}
        for r in t.records:
            if r.kind == "counter" and r.name == name:
                last[tuple(sorted(r.tags.items()))] = r.value
        return sum(last.values())

    # the legacy attribute surface reads exactly what the stream says
    assert sched.admissions == final_totals("sched.admissions") == \
        len(admissions)
    assert cache.plan_misses == final_totals("plan_cache.plan_miss")
    assert cache.plan_hits == final_totals("plan_cache.plan_hit")
    assert cache.hits == final_totals("plan_cache.step_hit")
    assert cache.misses == final_totals("plan_cache.step_miss")
    # structural cross-checks: one compiled trace per ADMITTED shape (the
    # plan cache also scores candidate shapes that are never admitted, so
    # plans >= compiled shapes)
    shapes = {(a.batch_rows, a.seq_len) for a in admissions}
    assert cache.misses == cache.traces == len(shapes) > 0
    assert cache.hits == len(admissions) - len(shapes)
    assert cache.plan_misses == len(cache.plans) >= len(shapes)
    assert cache.plan_hits > 0  # repeated scoring of known shapes
    assert final_totals("sched.submitted") == 10


def test_default_and_recording_trackers_agree():
    """The aggregate-only default sink and the recording sink see the
    same totals on the same drain — persistence must not change
    accounting."""
    t_rec, t_plain = RecordingTracker(), Tracker()
    cache_r, sched_r, _ = _mixed_drain(t_rec)
    cache_p, sched_p, _ = _mixed_drain(t_plain)
    assert (cache_r.hits, cache_r.misses, cache_r.plan_hits,
            cache_r.plan_misses, sched_r.admissions) == \
           (cache_p.hits, cache_p.misses, cache_p.plan_hits,
            cache_p.plan_misses, sched_p.admissions)


def test_calibrator_counters_through_tracker():
    """OnlineCalibrator's refit/recalibration tallies live in the
    tracker now; the attributes are reads of it."""
    from repro.serving.sched import CalibrationConfig, OnlineCalibrator
    from tests.test_sched import make_cache

    t = RecordingTracker()
    cache = make_cache(dp=2, tracker=t)
    choice = cache.select(4, 256)
    cal = OnlineCalibrator(
        cache, CalibrationConfig(min_samples=1, refit_every=1), tracker=t)
    assert cal.refits == 0 and cal.recalibrations == 0
    # wildly slower than predicted -> refit and (damped) drift
    for _ in range(3):
        cal.observe(choice, 4, 256, [choice.t_step * 50.0] * 4)
    assert cal.refits == 3
    assert cal.refits == t.counter("calibration.refits")
    assert t.series("calibration.measured_step_us",
                    {"rows": 4, "seq": 256}).n == 3
    drift_records = [r for r in t.records
                     if r.name == "calibration.drift_ratio"]
    assert drift_records and all(r.kind == "gauge" for r in drift_records)
    assert cal.recalibrations == t.counter("calibration.recalibrations")


def test_forecaster_publishes_gap_series():
    from repro.serving.sched import ArrivalForecaster

    t = RecordingTracker()
    f = ArrivalForecaster(tracker=t)
    f.observe(256, 0.0)  # first arrival: no gap yet
    assert t.series("forecast.mean_gap_s", {"seq": 256}).n == 0
    f.observe(256, 1.0)
    f.observe(256, 2.0)
    assert t.series("forecast.mean_gap_s", {"seq": 256}).n == 2
    assert t.series("forecast.mean_gap_s", {"seq": 256}).last == \
        pytest.approx(1.0)


# ---------------------------------------------------------------------------
# (f) span records (DESIGN.md §12): schema, nesting, crash safety
# ---------------------------------------------------------------------------

def test_span_event_record_shape():
    t = RecordingTracker()
    t.span_event("comm.leg", 0.25, 0.005, step=3, tags={"stream": "ring"})
    (r,) = t.records
    assert (r.kind, r.name, r.step) == ("span", "comm.leg", 3)
    assert r.t_start == pytest.approx(0.25)
    assert r.value == pytest.approx(0.005)
    assert validate_record(r.to_dict()) == []
    # durations aggregate like gauges, so summary() covers spans for free
    assert t.series("comm.leg", {"stream": "ring"}).n == 1
    # round-trips with t_start intact
    assert Record.from_dict(r.to_dict()) == r


def test_span_context_manager_times_and_nests():
    t = RecordingTracker()
    with t.span("engine.step", step=0):
        with t.span("plan_cache.trace", tags={"rows": 2}):
            pass
    inner, outer = t.records
    assert inner.name == "plan_cache.trace"
    assert inner.tags["parent"] == "engine.step"  # nesting is recorded
    assert outer.name == "engine.step" and "parent" not in outer.tags
    # the inner window is contained in the outer one
    assert outer.t_start <= inner.t_start
    assert inner.t_start + inner.value <= outer.t_start + outer.value + 1e-9
    for r in t.records:
        assert validate_record(r.to_dict()) == []


def test_span_emitted_even_on_exception():
    t = RecordingTracker()
    with pytest.raises(RuntimeError):
        with t.span("engine.step"):
            raise RuntimeError("boom")
    assert [r.name for r in t.records] == ["engine.step"]
    assert t._span_stack == []  # stack unwound


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.pop("t_start"), "t_start"),
    (lambda d: d.update(t_start=-0.5), "t_start"),
    (lambda d: d.update(t_start=True), "t_start"),
    (lambda d: d.update(value=-1.0), "negative"),
])
def test_validate_record_rejects_malformed_spans(mutate, needle):
    d = Record(name="s", value=1.0, kind="span", seq=0, t_start=0.0).to_dict()
    mutate(d)
    errs = validate_record(d)
    assert errs and any(needle in e for e in errs), errs


def test_t_start_forbidden_on_non_span_kinds():
    d = Record(name="g", value=1.0, kind="gauge", seq=0).to_dict()
    d["t_start"] = 0.5
    assert any("span" in e for e in validate_record(d))


def test_null_tracker_span_noop():
    t = NullTracker()
    with t.span("x"):
        t.span_event("y", 0.0, 1.0)
    assert t.series("y").n == 0


def test_spans_reach_the_profiler_host_plane(tmp_path):
    """A span opened under a profiler session is an event of the written
    xplane's host plane, with the caller's tags as its stats, whatever
    the sink; the tracker's own ``parent`` tag stays off the trace."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Tracker().span("engine.run_once"):
            with Tracker().span("engine.dispatch", step=0,
                                tags={"rows": 2, "seq": 1024}):
                pass
            with NullTracker().span("engine.sync", tags={"rows": 2}):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("engine."):
                        events[e.name] = (e.start_ns, e.end_ns,
                                          dict(e.stats))
    assert set(events) == {"engine.run_once", "engine.dispatch",
                           "engine.sync"}
    assert events["engine.dispatch"][2] == {"rows": 2, "seq": 1024}
    assert events["engine.sync"][2] == {"rows": 2}
    assert events["engine.run_once"][2] == {}
    outer = events["engine.run_once"]
    for name in ("engine.dispatch", "engine.sync"):
        assert outer[0] <= events[name][0] <= events[name][1] <= outer[1]


def test_id_tags_stay_on_records_not_in_aggregates():
    """Request and admission ids split no gauge or span series (the
    default sink stays bounded) but reach a persistent sink intact."""
    t = RecordingTracker()
    for i in range(50):
        t.log("engine.request_done", 0.5,
              tags={"adm": i, "rid": 1000 + i, "seq": 1024})
        t.span_event("engine.step", 0.0, 0.1, tags={"adm": i, "seq": 1024})
        t.log("engine.park", 1.0, tags={"adm": i, "rids": f"{i},{i + 1}"})
    assert [name for name, _ in t._stats] == [
        "engine.request_done", "engine.step", "engine.park"]
    assert t.series("engine.request_done", {"seq": 1024}).n == 50
    # a lookup by the record's full tags finds the same series
    assert t.series("engine.request_done",
                    {"adm": 7, "rid": 1007, "seq": 1024}).n == 50
    done = [r for r in t.records if r.name == "engine.request_done"]
    assert [r.tags["rid"] for r in done] == list(range(1000, 1050))
    assert done[3].tags == {"adm": 3, "rid": 1003, "seq": 1024}


def test_jsonl_crash_tail_recoverable(tmp_path):
    """A writer killed mid-record leaves a trace whose completed lines are
    all schema-valid; read_jsonl(partial_tail='drop') recovers them."""
    p = tmp_path / "t.jsonl"
    t = JsonlTracker(p)  # flush_every=1: every record hits the OS at once
    t.count("a", 1)
    with t.span("s"):
        pass
    t.log("g", 2.0)
    # crash simulation: truncate the final record mid-line, no close()
    t.flush()
    raw = p.read_bytes()
    p.write_bytes(raw[:-9])  # cut into the last JSON line
    for line in p.read_text().splitlines()[:-1]:
        assert validate_record(json.loads(line)) == []
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(p)  # default: corruption is an error
    recs = read_jsonl(p, partial_tail="drop")
    assert [r.name for r in recs] == ["a", "s"]
    assert recs[1].kind == "span"
    t.close()


def test_jsonl_flush_every_batches_but_close_flushes(tmp_path):
    p = tmp_path / "t.jsonl"
    t = JsonlTracker(p, flush_every=100)
    t.count("a", 1)
    t.count("a", 1)
    # unflushed: the OS may have nothing yet (can't assert emptiness
    # portably, but flush() must make both lines visible)
    t.flush()
    assert len(p.read_text().splitlines()) == 2
    t.count("a", 1)
    t.close()
    assert len(read_jsonl(p)) == 3


def test_jsonl_closes_on_exception(tmp_path):
    p = tmp_path / "t.jsonl"
    with pytest.raises(RuntimeError):
        with JsonlTracker(p, flush_every=1000) as t:
            t.count("a", 1)
            raise RuntimeError("serve crashed")
    assert t._fh is None  # context manager closed (and thus flushed) it
    assert [r.name for r in read_jsonl(p)] == ["a"]


def test_partial_tail_drop_does_not_mask_mid_file_corruption(tmp_path):
    p = tmp_path / "bad.jsonl"
    good = json.dumps(Record(name="n", value=1.0, kind="gauge",
                             seq=0).to_dict(), sort_keys=True)
    p.write_text('{"truncated' + "\n" + good + "\n")
    with pytest.raises(json.JSONDecodeError):
        read_jsonl(p, partial_tail="drop")
