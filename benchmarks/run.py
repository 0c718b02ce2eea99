"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  See each module's docstring for
the figure it regenerates and the derivation caveats (this container is
CPU-only; multi-pod numbers come from the calibrated analytical model,
not wall clocks).

Besides the CSV, every module run also lands in a ``BENCH_<module>.json``
trajectory record (``--out-dir``, default cwd): the module's rows (step
latencies — measured for device-local benches, model-predicted for
multi-pod sweeps) plus, for modules exposing ``records()``, structured
per-config records pairing each configuration with its comm-model
prediction breakdown.  These files are the calibration corpus the ROADMAP
"fit NetworkModel to BENCH_*.json" item consumes: the JSON keeps the full
(config -> prediction) mapping that the flat CSV derives away.

``--metrics out.jsonl`` additionally routes every parsed row through the
serving metrics sink (DESIGN.md §11): one ``bench.us`` gauge per row
(tagged module/name) and per-module ``bench.rows``/``bench.errors``
counters, schema-versioned like a serve trace — so bench trajectories
and serving telemetry are one stream format.  ``--only SUBSTR`` filters
modules by substring (CI's metrics-schema gate runs a single fast
module).
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time


def parse_row(line: str) -> dict:
    """Inverse of common.row: 'name,us,derived' (derived may hold commas).
    Non-finite latencies (error rows) become null so the JSON stays valid."""
    name, us, derived = (line.split(",", 2) + ["", ""])[:3]
    try:
        us_val: float | None = float(us)
        if not math.isfinite(us_val):
            us_val = None
    except ValueError:
        us_val = None
    return {"name": name, "us": us_val, "derived": derived}


def write_bench_json(out_dir: pathlib.Path, module_name: str,
                     rows: list[str], records: list[dict] | None) -> pathlib.Path:
    """Write one BENCH_<module>.json trajectory record."""
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"BENCH_{module_name}.json"
    records = [dict(r) for r in records] if records else []
    for rec in records:
        # surface the comm model's predicted overlap efficiency
        # (DESIGN.md §12) as a first-class column, next to the latency it
        # modulates — consumers should not have to dig in the breakdown
        if "overlap_efficiency" not in rec:
            bd = rec.get("predicted_breakdown") or {}
            rec["overlap_efficiency"] = bd.get("overlap_efficiency")
    payload = {
        "schema": "bench.v1",
        "module": module_name,
        "generated_at": time.time(),
        "rows": [parse_row(r) for r in rows],
        "records": records,
    }
    path.write_text(json.dumps(payload, indent=1, sort_keys=True))
    return path


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out-dir", type=pathlib.Path, default=pathlib.Path("."),
                    help="directory for BENCH_*.json trajectory records")
    ap.add_argument("--no-json", action="store_true",
                    help="CSV to stdout only; write no BENCH_*.json")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only modules whose name contains SUBSTR")
    ap.add_argument("--metrics", default=None, metavar="OUT.JSONL",
                    help="stream each row through the serving metrics "
                         "sink as schema-versioned JSONL (DESIGN.md §11)")
    ap.add_argument("--profile", default=None, metavar="TRACE.JSONL",
                    help="--metrics plus the span-level comm profiler "
                         "(DESIGN.md §12): device-executing modules also "
                         "stream per-device comm-leg/compute spans; render "
                         "with scripts/trace_report.py")
    args = ap.parse_args(argv)
    if args.profile is not None and args.metrics is not None:
        ap.error("--profile already streams metrics records; "
                 "give one output path, not both")

    import contextlib

    from repro.comm import CommProfiler, emit_leg_spans
    from repro.comm import profile as comm_profile
    from repro.serving.metrics import JsonlTracker, Tracker

    sink = args.profile if args.profile is not None else args.metrics
    tracker = JsonlTracker(sink) if sink is not None else Tracker()
    profiler = CommProfiler() if args.profile is not None else None

    from . import (
        ablation,
        comm_volume,
        config_sweep,
        e2e_latency,
        fleet_sweep,
        hier_a2a_sweep,
        hybrid_sweep,
        kernel_bench,
        layerwise,
        sched_sweep,
    )

    modules = {
        "comm_volume (Fig 3b / App D)": comm_volume,
        "e2e_latency (Fig 7)": e2e_latency,
        "config_sweep (Fig 8)": config_sweep,
        "layerwise (Fig 9)": layerwise,
        "ablation (Fig 10)": ablation,
        "kernel_bench (Fig 12)": kernel_bench,
        "hybrid_sweep (beyond-paper, DESIGN.md §7)": hybrid_sweep,
        "hier_a2a_sweep (beyond-paper, DESIGN.md §8.2)": hier_a2a_sweep,
        "sched_sweep (beyond-paper, DESIGN.md §9)": sched_sweep,
        "fleet_sweep (beyond-paper, DESIGN.md §13)": fleet_sweep,
    }
    if args.only is not None:
        modules = {t: m for t, m in modules.items() if args.only in t}
        if not modules:
            raise SystemExit(f"--only {args.only!r} matched no module")

    print("name,us_per_call,derived")
    ok = True
    for title, mod in modules.items():
        mod_name = mod.__name__.split(".")[-1]
        print(f"# --- {title} ---", file=sys.stderr)
        try:
            prof_ctx = (comm_profile(profiler) if profiler is not None
                        else contextlib.nullcontext())
            with prof_ctx:
                rows = list(mod.run())
            if profiler is not None:
                n_spans = emit_leg_spans(profiler, tracker)
                if n_spans:
                    print(f"# {mod_name}: {n_spans} profiler spans",
                          file=sys.stderr)
            for line in rows:
                print(line)
                parsed = parse_row(line)
                if parsed["us"] is not None:
                    tracker.log("bench.us", parsed["us"],
                                tags={"module": mod_name,
                                      "name": parsed["name"]})
            tracker.count("bench.rows", len(rows),
                          tags={"module": mod_name})
            if not args.no_json:
                recs = getattr(mod, "records", None)
                path = write_bench_json(args.out_dir, mod_name,
                                        rows, recs() if recs else None)
                print(f"# wrote {path}", file=sys.stderr)
        except Exception as e:  # keep the harness running, flag failure
            print(f"{title},NaN,ERROR:{type(e).__name__}:{e}")
            tracker.count("bench.errors", tags={"module": mod_name})
            ok = False
    tracker.close()
    if sink is not None:
        print(f"# wrote {sink}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
