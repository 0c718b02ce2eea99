"""One run of one benchmark cell on the chips of this machine.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Drives the served path, ``DiTServer.submit`` -> ``run_once`` (scheduler
and bucketer, the plan cache's jitted sampler step, ``dit_forward``,
``sp_attention`` and the XLA collectives), with weights, text embeddings
and traffic drawn from ``--seed``.  Set-up builds the weights on the
device, loads or compiles every (rows, latent length) program the
traffic can admit and runs two steps of each; then the window measures
``--seconds`` of open-loop traffic, the requests still open at its close
being drained, or serves a backlog mix's fixed set; then the float32
reference of the config's form (``forms/<form>.py``) replays a seeded
sample of the finished requests and decides ``correct``.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window (of its first
``trace_s`` seconds, where the mix bounds it), and on standard error the
seconds each stage of reading the trace took.  The last line
of standard output is one JSON object.  With no TPU, or fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any  # noqa: E402

from bench import flops, readers, spec, traffic  # noqa: E402

sys.path.insert(0, str(spec.ROOT / "src"))

WARM_RID = 1 << 29  # warm-up request ids, apart from the window's
RID_SPAN = 1 << 28  # window request ids start at a seeded offset below this


class _WarmedUp(Exception):
    """Raised from the server's step hook once a warm-up step is done."""


@dataclasses.dataclass
class Req:
    rid: int
    length: int
    due: float  # seconds after the window opened
    submitted: float | None = None
    started: float | None = None  # start of the run_once that returned it
    done: float | None = None  # that run_once's return
    latents: Any = None


@dataclasses.dataclass
class Batch:
    start: float
    end: float
    rows: int
    length: int


@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: spec.Cell
    seconds: float
    setup_s: float
    requests: list[Req]
    batches: list[Batch]
    traced_batches: list[Batch]
    trace: Any  # bench.trace.Trace, with --trace 1
    device_kind: str
    chips: int
    compiles_in_window: int

    def step_flops(self, rows: int, length: int) -> float:
        return flops.step_flops(self.cell.config, rows, length)

    @property
    def peak(self) -> dict:
        return spec.peak(self.device_kind)

    @property
    def num_steps(self) -> int:
        return int(self.cell.config["sampler"]["num_steps"])

    @functools.cached_property
    def part_mfu(self) -> dict[str, float]:
        """``readers.part_mfu`` of the trace, read once for every part's
        metric; empty without a trace of a chip."""
        if self.trace is None or not self.trace.devices:
            return {}
        return readers.part_mfu(self.trace, self.cell.config,
                                self.peak["bf16_flops_per_s"])


def traced_batches(batches: list[Batch], trace_end: float) -> list[Batch]:
    """The batches that a trace stopped at ``trace_end`` holds whole: the
    trace stops only after a ``run_once`` returns, so every batch that
    started before then ended inside it."""
    return [b for b in batches if b.start < trace_end]


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; exits 2 when there are not."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench.run: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench.run: the cell needs {chips} chips, JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def model_config(config: dict):
    """The program's config for ``config``; ValueError, naming the form,
    where the program would not be what the form's reference computes."""
    from repro.configs import get_config

    name = config["form"]
    form = spec.form(name)
    m = dict(config["model"])
    cfg = dataclasses.replace(get_config(m.pop("base")), **m)
    for k, want in form.PROGRAM_KEYS.items():
        if getattr(cfg, k) != want:
            raise ValueError(f"form {name}: {k}={getattr(cfg, k)!r} but "
                             f"the form implements {want!r}")
    for k, want in form.program_sizes(config).items():
        if config[k] != want:
            raise ValueError(f"form {name}: {k} {config[k]!r} but the "
                             f"program takes {want!r}")
    return cfg


class Harness:
    """A ``DiTServer`` for one cell, with the clocks the metrics read."""

    def __init__(self, cell: spec.Cell, seed: int, devices):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bench import weights
        from repro.compat import make_mesh
        from repro.core import SPConfig
        from repro.models import get_model
        from repro.serving import DiTServer, SamplerConfig
        from repro.serving.metrics import Tracker

        c = cell.config
        self.cell, self.seed = cell, seed
        self.cfg = model_config(c)
        init = spec.form(c["form"]).INIT
        mesh_shape = (c["mesh"]["data"], c["mesh"]["model"])
        self.mesh = make_mesh(mesh_shape, ("data", "model"),
                              devices=list(devices))
        sp = SPConfig(sp_axes=("model",), batch_axes=("data",), **c["sp"])
        self.sampler = SamplerConfig(**c["sampler"])
        key = weights.base_key(seed)
        struct = jax.eval_shape(
            lambda: get_model(self.cfg).init(self.cfg, jax.random.PRNGKey(0),
                                             1)[0])
        rep = NamedSharding(self.mesh, P())
        make = jax.jit(lambda k: (
            weights.make_params(struct, k, self.cfg.n_layers,
                                self.cfg.dtype, init),
            weights.cond_pool(k, c["text_tokens"], c["text_width"],
                              self.cfg.dtype)), out_shardings=rep)
        params, pool = make(key)
        self.conds = [pool[i] for i in range(weights.COND_POOL)]
        jax.block_until_ready((params, self.conds))
        self.tracker = Tracker()
        self.srv = DiTServer(params, self.cfg, self.mesh, sp,
                             sampler=self.sampler, max_batch=c["max_batch"],
                             tracker=self.tracker)
        self.by_rid: dict[int, Req] = {}
        self._t0 = 0.0

    def now(self) -> float:
        return time.perf_counter() - self._t0

    # -- set-up -----------------------------------------------------------
    def shapes(self) -> list[tuple[int, int]]:
        """Every (rows, latent length) the window can admit."""
        rows = range(1, self.cell.config["max_batch"] + 1)
        return [(b, t) for t in self.cell.traffic["lengths"] for b in rows]

    def warm_up(self) -> None:
        """Two sampler steps of every shape through ``run_once``, and the
        per-row result slicing ``run_once`` does after its last step."""
        import jax

        from repro.serving import DiTRequest

        def stop(_srv, i):
            # step 0 takes the noise, step 1 a step's output: the step
            # program compiles once for each of their shardings
            if i == 1:
                raise _WarmedUp

        # keep the step's output, to slice it as run_once would: the
        # slices compile for its sharding
        outputs = []
        cache = self.srv.plan_cache
        step_fn = cache.step_fn

        def keeping(*a, **k):
            fn = step_fn(*a, **k)

            def call(*args):
                outputs.append(fn(*args))
                return outputs[-1]

            return call

        cache.step_fn = keeping
        self.srv.on_step = stop
        self.tracker.persistent = True  # block on the step before stopping
        rid = WARM_RID
        try:
            for b, t in self.shapes():
                for _ in range(b):
                    self.srv.submit(DiTRequest(rid=rid, seq_len=t,
                                               cond=self.conds[rid % len(
                                                   self.conds)]))
                    rid += 1
                with contextlib.suppress(_WarmedUp):
                    self.srv.run_once()
                x = outputs.pop()
                jax.block_until_ready([x[i] for i in range(b)])
        finally:
            del cache.step_fn  # back to the class's method
            self.tracker.persistent = False
            self.srv.on_step = None

    # -- the window -------------------------------------------------------
    def submit(self, req: Req) -> None:
        import jax

        from repro.serving import DiTRequest

        with jax.profiler.TraceAnnotation("bench.submit"):
            self.srv.submit(DiTRequest(
                rid=req.rid, seq_len=req.length,
                cond=self.conds[req.rid % len(self.conds)]))
        req.submitted = self.now()
        self.by_rid[req.rid] = req

    def run_once(self, batches: list[Batch]) -> None:
        import jax

        start = self.now()
        with jax.profiler.TraceAnnotation("bench.run_once"):
            results = self.srv.run_once()
        end = self.now()
        for r in results:
            req = self.by_rid[r.rid]
            req.started, req.done, req.latents = start, end, r.latents
        if results:
            batches.append(Batch(start, end, len(results),
                                 self.by_rid[results[0].rid].length))

    def serve(self, seconds: float, on_close, rate: float | None = None,
              on_trace_end=lambda: None) -> tuple[list[Req], list[Batch]]:
        """Offer the cell's traffic: an open loop's for ``seconds``, then
        drain for at most the mix's ``drain_s``; a backlog's set until it
        is served, for at most ``drain_s``.  ``on_close`` runs once,
        after the first ``run_once`` that ends past the open window's
        close (a backlog's: once its set is served), ``on_trace_end``
        likewise past the mix's ``trace_s``, or with ``on_close`` where
        that comes first.  ``rate`` overrides the mix's rate (the knee
        sweep)."""
        import jax

        tr = self.cell.traffic
        rid0 = int(traffic.rng(self.seed).integers(RID_SPAN))
        batches: list[Batch] = []
        backlog = tr["loop"] == "backlog"
        close_at = math.inf if backlog else seconds
        trace_at = min(tr.get("trace_s", math.inf), close_at)
        limit = tr["drain_s"] + (0 if backlog else seconds)
        closed = trace_ended = False
        self._t0 = time.perf_counter()
        sched = traffic.schedule(tr, self.seed, seconds, rate)
        reqs = [Req(rid0 + i, a.length, a.due) for i, a in enumerate(sched)]
        nxt = 0
        while True:
            t = self.now()
            if t >= trace_at and not trace_ended:
                on_trace_end()
                trace_ended = True
            if t >= close_at and not closed:
                on_close()
                closed = True
            while nxt < len(reqs) and reqs[nxt].due <= t:
                self.submit(reqs[nxt])
                nxt += 1
            if nxt == len(reqs) and not self.srv.pending:
                break
            if t >= limit:
                break
            if self.srv.pending:
                self.run_once(batches)
            else:
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, reqs[nxt].due - self.now()))
        if not trace_ended:
            on_trace_end()
        if not closed:
            on_close()
        return reqs, batches


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def choose(finished: list[Req], seed: int, n: int) -> list[Req]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    if not finished:
        return []
    rng = traffic.rng(seed + 1)
    top = max(r.length for r in finished)
    longest = [r for r in finished if r.length == top]
    first = longest[int(rng.integers(len(longest)))]
    rest = [r for r in finished if r is not first]
    idx = rng.permutation(len(rest))[: max(0, n - 1)]
    return [first] + [rest[i] for i in sorted(idx)]


def compare(cell: spec.Cell, seed: int, sample: list[Req], conds,
            mode: str = "program", devices=None) -> list[float]:
    """Each sampled request's ``reference.rel_err`` against the float32
    reference on ``devices``: of the served latents (``mode="program"``)
    or of the reference computed in another precision (the control)."""
    import jax
    import numpy as np

    from bench import reference, weights

    c = cell.config
    form = spec.form(c["form"])
    n = form.Dims.of(c)
    key = weights.base_key(seed)
    g = c["sampler"].get("guidance_scale", 1.0)
    steps = c["sampler"]["num_steps"]
    out = []
    for r in sample:
        x0 = reference.initial_noise(r.rid, r.length, c["latent_channels"],
                                     n.dtype)
        cond = conds[r.rid % len(conds)]
        want = form.sample(key, n, x0, cond, steps, g, devices=devices)
        got = (r.latents if mode == "program"
               else form.sample(key, n, x0, cond, steps, g, mode=mode,
                                devices=devices))
        out.append(reference.rel_err(np.asarray(got, np.float32),
                                     jax.device_get(want), x0))
    return out


def verdict(cell: spec.Cell, finished: int, sample: list[Req],
            errs: list[float]) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit."""
    import numpy as np

    finite = all(bool(np.isfinite(r.latents).all()) for r in sample)
    limit = cell.config["check"]["rel_err_limit"]
    worst = max(errs) if errs else math.inf
    checks = {"finished": {"value": finished, "limit": 1},
              "finite": {"value": int(finite), "limit": 1},
              "rel_err_worst": {"value": worst, "limit": limit}}
    return bool(finished >= 1 and finite and worst <= limit), checks


def compile_cache() -> None:
    """The program's persistent compile cache (``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` names one), holding
    every program, however quick to compile."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, trace_dir: str | None = None,
             served: str = "program") -> dict:
    """One run on ``devices``; returns the result line's object.
    ``served="fp8"`` puts the control in the program's place: the
    sampled requests are judged on the reference computed with float8
    matmul operands instead of on the latents the window served."""
    import jax
    import numpy as np

    from bench import trace as trace_mod

    h = Harness(cell, seed, devices)
    h.warm_up()
    # set-up leaves long-lived objects (traced programs, caches): keep
    # the collector's full passes in the window off them
    gc.collect()
    gc.freeze()
    compiles = []

    def on_event(event: str, *_a, **_k) -> None:
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_hits"):
            compiles.append(event)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    log_dir = None
    traced: dict = {}
    if trace:
        log_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - T_PROCESS
    stages: dict[str, float] = {}  # seconds of each stage of the trace

    def timed(stage: str, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        stages[stage] = time.perf_counter() - t0
        return out

    def on_trace_end() -> None:
        traced["window_s"] = h.now()
        if trace:
            timed("stop_trace", jax.profiler.stop_trace)

    def on_close() -> None:
        traced["compiles"] = len(compiles)

    reqs, batches = h.serve(seconds, on_close, on_trace_end=on_trace_end)
    gc.unfreeze()
    peak_mem = memory_peak_bytes(devices)
    dev = devices[0]
    tr = None
    if trace:
        tr = timed("parse", trace_mod.load, timed("find", trace_mod.find,
                                                  log_dir))
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)
    run = Run(cell, seconds, setup_s, reqs, batches,
              traced_batches(batches, traced["window_s"]), tr,
              dev.device_kind, len(devices), traced["compiles"])
    names = cell.per_layer if trace else cell.end_to_end
    t_read = time.perf_counter()
    metrics = {}
    for name, entry in names.items():
        v = spec.metric_reader(name)(run)
        if v is not None:
            metrics[name] = {"value": v, "unit": entry["unit"]}
    if trace:
        busy = trace_mod.busy_s(tr)
        breakdown = {"device_ops": trace_mod.top_ops(tr),
                     "idle_gaps": trace_mod.idle_gaps(tr)}
        stages["readers"] = time.perf_counter() - t_read
        for scope, secs in readers.scope_seconds(tr).items():
            print(f"scope {scope}: {secs!r} s", file=sys.stderr)
        for name, secs in readers.stall_gaps(tr):
            print(f"stall in {name}: {secs!r} s", file=sys.stderr)
        for stage, secs in stages.items():
            print(f"trace stage {stage}: {secs!r} s", file=sys.stderr)
    print(f"compiles in window: {run.compiles_in_window}", file=sys.stderr)
    late = [r.submitted - r.due for r in reqs if r.submitted is not None]
    if late:
        print(f"generator late: max {max(late)!r} s, mean "
              f"{sum(late) / len(late)!r} s", file=sys.stderr)

    # correctness: the window's own outputs against the reference, with
    # the program's state freed first so that the reference sets no peak
    finished = [r for r in reqs if r.latents is not None]
    sample = choose(finished, seed, cell.config["check"]["requests"])
    keep = {r.rid for r in sample}
    for r in reqs:
        r.latents = (np.asarray(r.latents.astype(np.float32))
                     if r.rid in keep else None)
    conds = [np.asarray(c.astype(np.float32)) for c in h.conds]
    del h, on_close  # the closure holds the harness too
    gc.collect()
    t_ref = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        errs = compare(cell, seed, sample, conds, mode=served,
                       devices=devices)
    print(f"reference: {len(sample)} requests in "
          f"{time.perf_counter() - t_ref!r} s", file=sys.stderr)
    correct, checks = verdict(cell, len(finished), sample, errs)
    failed = sum(1 for r in reqs if r.done is None)
    out = {"correct": correct, "attempted": len(reqs), "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "memory_peak_bytes": peak_mem}}
    if trace:
        out["device"]["busy_s"] = busy
        out["device"]["window_s"] = traced["window_s"]
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    devices = tpu_devices(cell.chips)
    compile_cache()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   trace_dir=args.trace_dir)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
