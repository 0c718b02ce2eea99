"""The knee of an open-loop cell: the rate its traffic file should carry.

    python -m bench.sweep --workload <cell> --seed <n> --seconds <s> \
        --rates 1.0 1.5 2.0 ...

One process, one set-up; each rate gets a window of ``--seconds`` of the
cell's mix at that rate.  A rate is sustained when every request due in
the window is served and the queue drains within a few batches of the
window's close: ``queued_at_close``, the requests due in the window that
had not started by its close, stays at a few, where past the knee it
grows with the window.  The knee is the highest sustained rate.  Prints
one JSON line a rate.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench import readers, run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    devices = run.tpu_devices(cell.chips)
    run.compile_cache()
    h = run.Harness(cell, args.seed, devices)
    h.warm_up()
    for rate in args.rates:
        closed = {}
        reqs, batches = h.serve(args.seconds,
                                lambda: closed.setdefault("t", h.now()),
                                rate=rate)
        for r in reqs:
            r.latents = None
        out = run.Run(cell, args.seconds, 0.0, reqs, batches, [], None,
                      devices[0].device_kind, len(devices), 0)
        lat = readers.latencies(out)
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "served": sum(r.done is not None for r in reqs),
            "drain_s": max(b.end for b in batches) - args.seconds,
            "queued_at_close": sum(
                1 for r in reqs if r.due < args.seconds
                and (r.started is None or r.started >= args.seconds)),
            "rows_per_batch": sum(b.rows for b in batches) / len(batches),
            "latency_p50_s": readers.percentile(lat, 50),
            "latency_p90_s": readers.percentile(lat, 90),
            "queue_wait_p90_s": readers.percentile(readers.queue_waits(out),
                                                   90),
            "busy_share": sum(b.end - b.start for b in batches)
            / max(b.end for b in batches)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
