"""Device time of the sampler step's ops under the program's ``sp_comm``
named scope (the SP exchange: Ulysses all-to-alls, ring and torus
permutes) over the step's device time, both summed over the chips (%).
None without a trace of a chip, or where no op of the step carries the
scope (a program without it, or SP=1)."""
from bench import readers

SCOPE = "sp_comm"


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    comm = total = 0.0
    for chip, paths in enumerate(tr.paths):
        for _, ops in readers.step_runs(tr, chip):
            for s, e, name in ops:
                total += e - s
                if SCOPE in paths.get(name, "").split("/"):
                    comm += e - s
    return 100.0 * comm / total if comm > 0 else None
