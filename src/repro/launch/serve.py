"""Production serving launcher: DiT sampling service or AR decode service.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch flux-12b --reduced --requests 4
    ... --arch flux-12b --reduced --requests 6 --mixed --sla 30   (scheduler)
    ... --arch qwen2-1.5b --reduced --requests 4   (AR decode)

DiT requests go through the SLA-aware request scheduler (DESIGN.md §9):
``--mixed`` submits a mixed-resolution queue (seq, seq/2, 2*seq cycling)
so the resolution bucketer and per-bucket plan cache are exercised;
``--sla`` attaches a deadline to every request and the admission policy
scores buckets by deadline slack against the comm model's predicted
batch latency.

The adaptive control loop (DESIGN.md §10) is opt-in per feedback path:
``--preempt`` lets an SLA-critical bucket park the running batch between
sampler steps, ``--recalibrate`` refits the comm model from measured
step times in-flight, ``--forecast`` bounds padded-batch deferral with
the per-bucket arrival forecast.

``--metrics out.jsonl`` (DESIGN.md §11) attaches a ``JsonlTracker`` to
the engine: every plan-cache hit/miss, admission, per-step wall clock,
preemption, resync and recalibration streams to ``out.jsonl`` as
schema-versioned records, and an end-of-run aggregate table is printed.
A persistent sink opts the step loop into per-step timing even without
``--preempt``/``--recalibrate``.

``--profile trace.jsonl`` (DESIGN.md §12) is ``--metrics`` plus the
span-level comm-runtime profiler: per-device comm-leg and compute spans
from inside the jitted step, host-side engine/plan-cache/calibration
spans, all into the same JSONL stream.  Render it with
``scripts/trace_report.py trace.jsonl --chrome trace.json`` (Perfetto
timeline + overlap-efficiency table + comm-model residuals).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from ..configs import get_config, get_reduced
from ..core import SPConfig
from ..models import get_model
from ..serving import (
    ARRequest,
    ARServer,
    CalibrationConfig,
    ControlConfig,
    DiTRequest,
    DiTServer,
    JsonlTracker,
    PreemptionPolicy,
    SCHEMA_VERSION,
    SamplerConfig,
    Tracker,
)
from .cache import enable_compile_cache
from .mesh import make_host_mesh, make_production_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--strategy", default="swift_torus")
    ap.add_argument("--mesh", choices=["pod", "multipod", "host"], default="host")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=4, help="sampling steps (DiT)")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed-resolution queue (exercises the bucketer)")
    ap.add_argument("--sla", type=float, default=None,
                    help="deadline (s) attached to every DiT request")
    ap.add_argument("--preempt", action="store_true",
                    help="step-level preemption for SLA-critical buckets "
                         "(DESIGN.md §10)")
    ap.add_argument("--recalibrate", action="store_true",
                    help="refit the comm model from measured step times "
                         "in-flight (DESIGN.md §10)")
    ap.add_argument("--forecast", action="store_true",
                    help="bound padded-batch deferral with the arrival "
                         "forecaster (DESIGN.md §10; deferral applies to "
                         "dp-padded batches, so this needs --data > 1)")
    ap.add_argument("--metrics", default=None, metavar="OUT.JSONL",
                    help="stream schema-versioned metrics records to this "
                         "JSONL file and print an end-of-run aggregate "
                         "table (DESIGN.md §11)")
    ap.add_argument("--profile", default=None, metavar="TRACE.JSONL",
                    help="--metrics plus the span-level comm-runtime "
                         "profiler (DESIGN.md §12); render the trace with "
                         "scripts/trace_report.py.  DiT only.")
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()
    if args.profile is not None and args.metrics is not None:
        ap.error("--profile already streams metrics records; "
                 "give one output path, not both")
    enable_compile_cache()

    if args.mesh == "host":
        mesh = make_host_mesh(model=args.model, data=args.data)
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "multipod")
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg, dtype="float32", sharding_overrides=())
    bundle = get_model(cfg)
    params, _ = bundle.init(cfg, jax.random.PRNGKey(0), mesh.shape["model"])

    sp_degree = mesh.shape["model"]
    sp = SPConfig(strategy=args.strategy if sp_degree > 1 else "full",
                  sp_axes=("model",), batch_axes=("data",))

    sink = args.profile if args.profile is not None else args.metrics
    tracker = JsonlTracker(sink) if sink is not None else Tracker()
    if args.profile is not None and cfg.family != "dit":
        ap.error("--profile instruments the DiT step loop; "
                 "use a dit --arch")
    if cfg.family == "dit":
        control = ControlConfig(
            preemption=PreemptionPolicy() if args.preempt else None,
            calibration=CalibrationConfig() if args.recalibrate else None,
            forecast=args.forecast)
        srv = DiTServer(params, cfg, mesh, sp,
                        sampler=SamplerConfig(num_steps=args.steps),
                        control=control, tracker=tracker,
                        profile=args.profile is not None)
        lens = ([args.seq, args.seq // 2, args.seq * 2] if args.mixed
                else [args.seq])
        for i in range(args.requests):
            srv.submit(DiTRequest(rid=i, seq_len=lens[i % len(lens)],
                                  sla=args.sla))
        for r in sorted(srv.serve(), key=lambda r: r.rid):
            print(f"request {r.rid}: latents {tuple(r.latents.shape)} "
                  f"latency {r.latency * 1e3:.1f} ms"
                  + ("" if r.sla_met else "  SLA MISSED"))
        tot = srv.scheduler.totals()
        print(f"scheduler: {tot.batches} batches over "
              f"{len(srv.plan_cache.plans)} bucket shapes "
              f"({srv.plan_cache.traces} traces, {srv.plan_cache.hits} "
              f"step-cache hits), {tot.padded_rows} padded rows, "
              f"max wait {tot.max_wait * 1e3:.1f} ms")
        if control.engaged:
            cal = srv.calibrator
            print(f"control: {srv.preemptions} preemptions "
                  f"({srv.scheduler.preempted} requests requeued)"
                  + (f", {cal.refits} refits / {cal.recalibrations} "
                     f"recalibrations ({srv.plan_cache.invalidations} "
                     f"plan-score invalidations)" if cal else ""))
    else:
        srv = ARServer(params, cfg, mesh, sp, batch_slots=4,
                       max_len=args.seq, tracker=tracker)
        for i in range(args.requests):
            srv.submit(ARRequest(rid=i,
                                 prompt=jnp.arange(1, 4 + i, dtype=jnp.int32),
                                 max_new_tokens=8))
        for rid, toks in sorted(srv.serve().items()):
            print(f"request {rid}: -> {toks}")
    if sink is not None:
        tracker.close()
        print(f"\nmetrics: wrote {tracker.path} (schema {SCHEMA_VERSION})")
        print(tracker.format_summary())
        if args.profile is not None:
            print(f"profile: render with scripts/trace_report.py "
                  f"{tracker.path} --chrome {tracker.path}.chrome.json")


if __name__ == "__main__":
    main()
