"""Bring-up smoke test: the served DiT path on a TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the SP=4 path on a four-chip host

Drives ``DiTServer`` (scheduler -> plan cache -> jitted sampler step -> SP
attention) with flux-12b at its published widths (d 3072, 24 x 128 heads,
d_ff 12288, bf16), depth cut to 16 blocks, weights drawn from ``--seed``
and perturbed off the adaLN-zero identity.  It checks what comes out:

  one chip   3 requests at 4096 latent tokens (1024^2 images) and 1 at
             1024 (512^2), 4 sampler steps: finite latents of the right
             shapes from exactly 2 plan-cache traces, and one served bf16
             sampler step of a 2-block cut against a float32 run of the
             same weights.
  --chips 4  2 requests at 16384 tokens (2048^2) on mesh (data=1,
             model=4), swift_torus over XLA collectives; then one float32
             sampler step of a 2-block cut at 4096 tokens under swift_torus
             (xla) and under ring with the Pallas channel (ring_flash and
             the in-kernel remote put), each against strategy="full" on
             one device.

Runs in one process and needs a TPU: with no TPU it exits non-zero before
printing any result.  The last line of a passing run is one JSON object
naming the device.  A failed check exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

BLOCKS = 16  # depth cut; the flux-12b stack is uniform, one block a period
REF_BLOCKS = 2  # depth of the cut the reference comparisons run on
STEPS = 4
DT = 1.0 / STEPS
# bf16 served step against its float32 run: bf16 keeps 8 mantissa bits
# (relative rounding 2^-9 per op), so a 2-block step lands near 1e-2
BF16_BOUND = 5e-2
# float32 SP step against float32 strategy="full": same maths, another
# reduction order; the Pallas kernel's in-kernel dots are not governed by
# jax.default_matmul_precision, so its bound allows one bf16 pass
SP_BOUND = {"xla": 1e-3, "pallas": 2e-2}


def perturbed_params(cfg, seed: int):
    """Model weights from ``seed``, every leaf nudged by half its fan-in
    init scale: a fresh DiT is the identity (adaLN-zero ``ada`` and
    ``proj_out``), which would make every comparison vacuous."""
    import jax

    from repro.models import get_model

    params, _ = get_model(cfg).init(cfg, jax.random.PRNGKey(seed), 1)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    out = []
    for (path, a), k in zip(flat, keys):
        # stacked block weights carry a leading layer dim
        core = a.shape[1:] if path[0].key == "layers" else a.shape
        scale = 0.5 * core[0] ** -0.5 if len(core) >= 2 else 0.05
        out.append(a + scale * jax.random.normal(k, a.shape, a.dtype))
    return jax.tree.unflatten(treedef, out)


def cut(params, cfg, blocks: int, dtype: str):
    """The first ``blocks`` blocks of ``params``, cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    p = dict(params, layers=jax.tree.map(lambda a: a[:blocks],
                                         params["layers"]))
    p = jax.tree.map(lambda a: a.astype(jnp.dtype(dtype)), p)
    return p, dataclasses.replace(cfg, n_layers=blocks, dtype=dtype)


def rel_err(got, want) -> float:
    """Relative L2 error, on the host (the two may live on different
    devices)."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def step_update(params, cfg, mesh, sp, x, cond):
    """One served sampler step at t = 1; returns its update x' - x."""
    import jax
    import jax.numpy as jnp

    from repro.models import ParallelContext
    from repro.serving import SamplerConfig
    from repro.serving.sampler import sample_step

    ctx = ParallelContext(mesh, sp, "prefill")
    sc = SamplerConfig(num_steps=STEPS)
    fn = jax.jit(lambda p, x, c: sample_step(p, cfg, ctx, x, c,
                                             jnp.float32(1.0), DT, sc))
    y = fn(params, x, cond)
    return y.astype(jnp.float32) - x.astype(jnp.float32)


def ref_inputs(cfg, tokens: int, seed: int, dtype: str):
    import jax
    import jax.numpy as jnp

    from repro.models.dit import LATENT_CHANNELS

    kx, kc = jax.random.split(jax.random.PRNGKey(seed + 2))
    x = jax.random.normal(kx, (1, tokens, LATENT_CHANNELS), jnp.float32)
    cond = jax.random.normal(kc, (1, cfg.text_tokens, cfg.cond_width),
                             jnp.float32)
    return x.astype(dtype), cond.astype(dtype)


class Checks:
    """Named checks, each printed with its value and bound."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        print(f"check {name}: {'ok' if ok else 'FAIL'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)

    def bound(self, name: str, err: float, bound: float) -> None:
        self(name, err <= bound, f"rel err {err!r} <= {bound!r}")


def serve(params, cfg, mesh, sp, lens: list[int], check: Checks,
          traces: int) -> None:
    """Serve one request per entry of ``lens`` through ``DiTServer`` and
    check the results: finite latents, their shapes, the trace count."""
    import jax
    import jax.numpy as jnp

    from repro.models.dit import LATENT_CHANNELS
    from repro.serving import DiTRequest, DiTServer, SamplerConfig

    compile_s: list[float] = []

    def on_compile(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    marks: list[float] = []  # compile seconds up to each batch's step 0

    def on_step(srv, i: int) -> None:
        if i == 0:
            marks.append(sum(compile_s))

    srv = DiTServer(params, cfg, mesh, sp,
                    sampler=SamplerConfig(num_steps=STEPS),
                    max_batch=max(lens.count(n) for n in lens))
    srv.on_step = on_step
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    t0 = time.perf_counter()
    for rid, n in enumerate(lens):
        srv.submit(DiTRequest(rid=rid, seq_len=n))
    results = srv.serve()
    wall = time.perf_counter() - t0
    # batches complete in admission order; each batch is one bucket
    order = list(dict.fromkeys(lens[r.rid] for r in results))
    for seq, before, upto in zip(order, [0.0] + marks, marks):
        rows = lens.count(seq)
        print(f"compile bucket {seq} tokens x {rows} rows: "
              f"{upto - before!r} s", flush=True)
    for r in sorted(results, key=lambda r: r.rid):
        print(f"request {r.rid}: {lens[r.rid]} tokens, latency "
              f"{r.latency!r} s", flush=True)
    print(f"served {len(results)} requests in {wall!r} s", flush=True)
    check("all requests served", sorted(r.rid for r in results)
          == list(range(len(lens))), f"{len(results)} of {len(lens)}")
    check("latent shapes", all(
        tuple(r.latents.shape) == (lens[r.rid], LATENT_CHANNELS)
        for r in results))
    check("latents finite", all(bool(jnp.isfinite(r.latents).all())
                                for r in results))
    check("plan-cache traces", srv.plan_cache.traces == traces,
          f"{srv.plan_cache.traces} == {traces}")


def one_chip(cfg, seed: int, check: Checks, tokens=(4096, 4096, 4096, 1024),
             ref_tokens: int = 4096) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import SPConfig
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(model=1, data=1)
    sp = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
    params = perturbed_params(cfg, seed)
    serve(params, cfg, mesh, sp, list(tokens), check,
          traces=len(set(tokens)))

    bf, cfg_bf = cut(params, cfg, REF_BLOCKS, "bfloat16")
    f32, cfg_f32 = cut(params, cfg, REF_BLOCKS, "float32")
    del params
    x, cond = ref_inputs(cfg, ref_tokens, seed, "bfloat16")
    got = step_update(bf, cfg_bf, mesh, sp, x, cond)
    with jax.default_matmul_precision("highest"):
        want = step_update(f32, cfg_f32, mesh, sp, x.astype("float32"),
                           cond.astype("float32"))
    size = float(jnp.linalg.norm(want) / jnp.linalg.norm(x.astype("float32")))
    check("step is not the identity", size > 1e-3,
          f"|update| / |x| = {size!r}")
    check.bound(f"bf16 step vs float32 ({REF_BLOCKS} blocks, "
                f"{ref_tokens} tokens)", rel_err(got, want), BF16_BOUND)


def four_chips(cfg, seed: int, check: Checks, tokens: int = 16384,
               ref_tokens: int = 4096) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.compat import make_mesh, pallas_interpret
    from repro.core import SPConfig
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    mesh = make_host_mesh(model=n, data=1)
    sp = SPConfig(strategy="swift_torus", sp_axes=("model",),
                  batch_axes=("data",))
    # weights live replicated on the mesh, as a deployment would hold them
    params = jax.device_put(perturbed_params(cfg, seed),
                            NamedSharding(mesh, P()))
    serve(params, cfg, mesh, sp, [tokens, tokens], check, traces=1)

    f32, cfg_f32 = cut(params, cfg, REF_BLOCKS, "float32")
    del params
    x, cond = ref_inputs(cfg, ref_tokens, seed, "float32")
    one = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    full = SPConfig(strategy="full", sp_axes=("model",), batch_axes=("data",))
    with jax.default_matmul_precision("highest"):
        want = step_update(jax.device_put(f32, jax.devices()[0]), cfg_f32,
                           one, full, x, cond)
        for strategy, backend in (("swift_torus", "xla"), ("ring", "pallas")):
            sp = SPConfig(strategy=strategy, sp_axes=("model",),
                          batch_axes=("data",), comm_backend=backend)
            got = step_update(f32, cfg_f32, mesh, sp, x, cond)
            check.bound(f"{strategy}/{backend} SP={n} vs full "
                        f"({REF_BLOCKS} blocks, {ref_tokens} tokens, "
                        f"float32, interpret={pallas_interpret()})",
                        rel_err(got, want), SP_BOUND[backend])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {count}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.cache import enable_compile_cache

    print(f"device: {dev.device_kind} x {count} ({dev.platform})", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = dataclasses.replace(get_config("flux-12b"), n_layers=BLOCKS)
    check = Checks()
    (four_chips if args.chips == 4 else one_chip)(cfg, args.seed, check)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
