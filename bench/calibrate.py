"""Readings that set a cell's correctness limit, on the chip.

    python -m bench.calibrate --workload <cell> --seconds <s> \
        --seeds 101 102 ... [--control-seeds 101 102 103]

For every seed, in one process: the cell's weights from that seed, a
short window of the cell's own traffic through the served path, and the
same seeded sample of finished requests that ``bench.run`` checks.  It
prints, one JSON line a seed, each sampled request's relative error
against the float32 reference (``program``) and, for the control seeds,
the error of the control, the reference computed with float8_e4m3fn
matmul operands, one precision step below the served bfloat16
(``control``), each with ``bench.run.verdict``'s decision on it under
the cell's limit (``program_correct``, ``control_correct``).  The
benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from bench import run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    cell = spec.cell(args.workload)
    devices = run.tpu_devices(cell.chips)
    run.compile_cache()
    n = cell.config["check"]["requests"]
    for seed in args.seeds:
        h = run.Harness(cell, seed, devices)
        h.warm_up()
        reqs, _ = h.serve(args.seconds, lambda: None)
        finished = [r for r in reqs if r.latents is not None]
        sample = run.choose(finished, seed, n)
        for r in sample:
            r.latents = np.asarray(r.latents.astype(np.float32))
        conds = [np.asarray(c.astype(np.float32)) for c in h.conds]
        count = len(finished)
        del h, reqs, finished
        gc.collect()
        line = {"seed": seed, "lengths": [r.length for r in sample]}
        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            modes = ["program"] + (["control"] if seed in args.control_seeds
                                   else [])
            for mode in modes:
                errs = run.compare(cell, seed, sample, conds,
                                   mode="fp8" if mode == "control" else mode,
                                   devices=devices)
                line[mode] = errs
                line[f"{mode}_correct"] = run.verdict(cell, count, sample,
                                                      errs)[0]
                if mode == "program":
                    line["reference_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
