"""HLO overlap validation gate (CI step; DESIGN.md §8).

Traces the two comm-heaviest programs — swift_torus attention and the
displaced patch pipeline — on an 8-fake-device CPU mesh, records their
intended one-sided schedules (repro.comm.trace), compiles, and validates:

  * every channel put appears as a collective-permute with the intended
    route (device pairs), and
  * every declared overlap (torus hops vs attend compute, ring rotation
    vs attend, pipe hand-off vs stage compute) is admissible in the
    compiled program.

The gate then runs ONCE MORE with ``backend="pallas"`` (DESIGN.md §8.1,
interpret mode): the same swift_torus program through the Pallas channel
backend + fused ring kernel, validating (a) the emulation branch's wire
moves still carry the intended routes in HLO and (b) the recorded
semaphore schedule is a valid protocol pairing — every put signaled
exactly once, no wait-before-put, and no blocking wait before the last
compute block of a fused step.

Exit code 1 on any failure, so schedule regressions (a barrier that
serialises a put, a refactor that silently drops a transfer or fires a
semaphore twice) fail fast.

    python -m repro.launch.commcheck

``--profile trace.jsonl`` additionally EXECUTES the validated programs
under the span profiler (DESIGN.md §12) and streams per-device comm-leg
and compute spans to the given JSONL file — the measured counterpart of
the intended schedules this gate validates statically.  Render with
``scripts/trace_report.py``.
"""
from __future__ import annotations

import argparse
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default=None, metavar="TRACE.JSONL",
                    help="also execute the validated programs under the "
                         "span profiler and write the trace here")
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from .. import comm
    from ..configs import get_reduced
    from ..core import SPConfig, sp_attention
    from ..core.pipefusion import KVState, PipelineConfig
    from ..models import ParallelContext, get_model
    from ..models.dit import dit_forward_displaced
    from ..serving import SamplerConfig
    from ..serving.sampler import hybrid_state_shape
    from ..compat import make_mesh
    from .mesh import make_hybrid_mesh

    assert len(jax.devices()) == 8, "commcheck needs 8 (fake) devices"
    reports = []

    # --- 1. swift_torus attention: torus hops + ring rotations ----------
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    sp = SPConfig(strategy="swift_torus", sp_axes=("pod", "model"),
                  batch_axes=("data",))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (2, 32, 2, 16))  # 2 heads => P_u = P_r = 2
    k = jax.random.normal(kk, (2, 32, 2, 16))
    v = jax.random.normal(kv, (2, 32, 2, 16))
    with comm.record("swift_torus") as tr:
        lowered = jax.jit(
            lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=sp)
        ).lower(q, k, v)
    # an empty trace must never pass the gate: both the torus hops and the
    # intra-ring rotations are expected on this (P_u=2, P_r=2) plan
    for want in ("torus", "ring"):
        if not any(e.stream == want for e in tr.events):
            print(f"commcheck FAIL: no '{want}' channel puts recorded in the "
                  "swift_torus trace")
            return 1
    reports.append(comm.validate(tr, lowered.compile().as_text(), mesh))

    # --- 2. displaced patch pipeline: pipe-axis stage hand-off ----------
    hmesh = make_hybrid_mesh(cfg=1, pipe=2, data=1, model=4)
    cfg = dataclasses.replace(get_reduced("flux-12b"), dtype="float32",
                              n_heads=4, n_kv_heads=4)
    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(1), 1)
    psp = SPConfig(strategy="swift_torus", sp_axes=("model",),
                   batch_axes=("data",), pp_axis="pipe")
    ctx = ParallelContext(hmesh, psp, "prefill")
    sc = SamplerConfig(num_steps=2,
                       pipeline=PipelineConfig(pp=2, warmup_steps=1))
    seq = 32
    lat = jax.random.normal(jax.random.PRNGKey(2), (1, seq, 64), jnp.float32)
    cond = jax.random.normal(jax.random.PRNGKey(3),
                             (1, cfg.text_tokens, cfg.cond_width),
                             jnp.float32)
    state = hybrid_state_shape(cfg, 1, seq, sc)
    tt = jnp.full((1,), 0.5, jnp.float32)

    def step(lat, cond, sk, sv):
        return dit_forward_displaced(params, cfg, ctx, latents=lat, cond=cond,
                                     timesteps=tt, kv_state=KVState(sk, sv),
                                     num_patches=2, pp=2)

    with comm.record("displaced_pipe") as tr:
        lowered = jax.jit(step).lower(lat, cond, state.k, state.v)
    if not any(e.stream == "pipe" for e in tr.events):
        print("commcheck FAIL: no pipe hand-off recorded in the displaced "
              "pipeline trace")
        return 1
    reports.append(comm.validate(tr, lowered.compile().as_text(), hmesh))

    # --- 2b. hierarchical two-level a2a (DESIGN.md §8.2): ulysses over
    # both boundaries with u_groups = N — the fast leg must stay inside
    # the machine, the slow leg's hops must declare-and-admit overlap ----
    hier_cfg = SPConfig(strategy="ulysses", sp_axes=("pod", "model"),
                        batch_axes=("data",), hier_a2a=True)
    hq = jax.random.normal(kq, (2, 32, 4, 16))  # 4 heads => P_u = 4, N = 2
    hk = jax.random.normal(kk, (2, 32, 4, 16))
    hv = jax.random.normal(kv, (2, 32, 4, 16))
    with comm.record("hier_a2a") as tr:
        lowered = jax.jit(
            lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=hier_cfg)
        ).lower(hq, hk, hv)
    hier_events = [e for e in tr.events if e.stream.startswith("hier")]
    labels = {e.channel.rsplit(".", 1)[-1] for e in hier_events}
    if not {"intra1", "inter1"} <= labels:
        print("commcheck FAIL: hierarchical a2a recorded no intra+inter "
              f"legs (channels: {sorted(labels)})")
        return 1
    m_fast = mesh.shape["model"]
    for e in hier_events:
        if "intra" in e.channel and any(s // m_fast != d // m_fast
                                        for s, d in e.perm):
            print(f"commcheck FAIL: fast leg {e.channel} crosses the "
                  f"machine boundary: {e.perm}")
            return 1
    if not all(e.overlaps for e in hier_events if "inter" in e.channel):
        print("commcheck FAIL: a hier inter hop declares no overlap")
        return 1
    reports.append(comm.validate(tr, lowered.compile().as_text(), mesh))

    # same program through the Pallas channel backend (interpret mode):
    # routes still present in HLO, semaphore protocol clean
    hier_pl = dataclasses.replace(hier_cfg, comm_backend="pallas")
    with comm.record("hier_a2a_pallas") as tr:
        lowered = jax.jit(
            lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=hier_pl)
        ).lower(hq, hk, hv)
    if not any(e.backend == "pallas" and e.stream.startswith("hier")
               for e in tr.events):
        print("commcheck FAIL: no pallas-backend hier puts recorded")
        return 1
    reports.append(comm.validate(tr, lowered.compile().as_text(), mesh,
                                 require_overlap=False))
    hier_sem = comm.validate_semaphores(tr)
    if not hier_sem.ok:
        print(hier_sem.summary())
        return 1

    # --- 3. Pallas backend (DESIGN.md §8.1): same swift_torus program,
    # semaphore-tracked channels + fused ring kernel, interpret mode -----
    psp = dataclasses.replace(sp, comm_backend="pallas")
    with comm.record("swift_torus_pallas") as tr:
        lowered = jax.jit(
            lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=psp)
        ).lower(q, k, v)
    if not any(e.backend == "pallas" for e in tr.events):
        print("commcheck FAIL: no pallas-backend puts recorded in the "
              "swift_torus_pallas trace")
        return 1
    if not tr.sem_events:
        print("commcheck FAIL: pallas backend recorded no semaphore events")
        return 1
    # route presence still holds on the emulation branch (the wire move is
    # a ppermute with the same pairs); overlap of the fused puts is the
    # kernel's own schedule, validated at the semaphore level below, so
    # HLO-level overlap admission is not required here.
    reports.append(comm.validate(tr, lowered.compile().as_text(), mesh,
                                 require_overlap=False))
    sem_rep = comm.validate_semaphores(tr)
    print(sem_rep.summary())

    ok = sem_rep.ok
    for rep in reports:
        print(rep.summary())
        ok &= rep.ok

    # --- 4. optional measured-schedule trace (DESIGN.md §12) ------------
    if ok and args.profile is not None:
        from ..serving import JsonlTracker
        tracker = JsonlTracker(args.profile)
        prof = comm.CommProfiler()
        with comm.profile(prof):
            # fresh lambdas: the profiler's callbacks are baked in at
            # trace time, so the validated-but-unprofiled jits above are
            # not reusable here
            jax.block_until_ready(jax.jit(
                lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=sp)
            )(q, k, v))
            jax.block_until_ready(jax.jit(
                lambda q, k, v: sp_attention(q, k, v, mesh=mesh, cfg=psp)
            )(q, k, v))
        n = comm.emit_leg_spans(prof, tracker)
        tracker.close()
        print(f"profile: wrote {n} spans to {tracker.path} "
              "(render with scripts/trace_report.py)")
        if n == 0:
            print("commcheck FAIL: profiled run produced no spans")
            return 1
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
