"""SP strategy dispatch: full | ring | ulysses | usp | swift | swift_torus.

This is the public entry point models call for distributed attention.  It
owns the ``shard_map`` over the SP mesh axes; everything outside attention
remains plain GSPMD.

Strategies (P = SP degree, N = machines/pods, M = chips per pod):
  full        — no SP; single-device reference (debug / tiny meshes).
  ring        — Ring Attention over the whole SP group (P_u = 1).
  ulysses     — Ulysses Attention over the whole SP group (P_r = 1,
                monolithic all-to-all).  Requires P | gcd(Hq, Hkv).
  usp         — USP baseline [5]: Ulysses intra-machine, Ring inter.
  swift       — SwiftFusion TAS (§4.2): Ulysses *inter*-machine, Ring
                *intra*; monolithic all-to-alls (the paper's "TAS" ablation).
  swift_torus — TAS + Torus Attention (§4.3): chunked all-to-all overlapped
                with compute, one-sided-style ppermute stages (full SFU).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import pallas_interpret
from ..kernels.ops import flash_attention
from . import planner
from .collectives import GroupLayout
from .ring import ORACLE, FlashLowering, ring_attention
from .softmax import reference_attention, MaskSpec
from .torus import torus_attention
from .ulysses import gather_qkv, group_positions, scatter_o

STRATEGIES = ("full", "ring", "ulysses", "usp", "swift", "swift_torus")
MACHINE_AXIS = "pod"  # the slow-boundary mesh axis (paper's N)


@dataclasses.dataclass(frozen=True)
class SPConfig:
    """How attention is distributed on the mesh."""

    strategy: str = "swift_torus"
    sp_axes: tuple[str, ...] = ("model",)  # sequence-parallel mesh axes
    batch_axes: tuple[str, ...] | None = ("data",)  # batch (DP) mesh axes
    replicate_kv: bool = False  # allow P_u up to gcd(SP, Hq) by replicating KV
    # Hybrid-parallel axes (DESIGN.md §7).  cfg_axis: the 2-way classifier-
    # free-guidance axis — the sampler stacks the cond/uncond branches on
    # the batch dim and this axis shards them, so attention (and, via GSPMD
    # propagation, the whole block) computes the two branches on disjoint
    # mesh halves.  pp_axis: the patch-pipeline stage axis — never touched
    # by attention itself (it partitions the *layer* dim of the weights);
    # named here so planners/engines can find it.
    cfg_axis: str | None = None
    pp_axis: str | None = None
    # Comm lowering (DESIGN.md §8.1): "xla" = ppermute + barrier, overlap
    # left to XLA's scheduler; "pallas" = in-kernel DMA + semaphores (the
    # fused ring_flash path).
    comm_backend: str = "xla"
    # Hierarchical a2a (DESIGN.md §8.2): decompose every Ulysses
    # all-to-all into an intra-machine exchange plus staged inter-machine
    # hops whenever the Ulysses groups span machines (engages only when
    # the topology qualifies: ulysses-outer placement, N > 1, N | P_u,
    # P_u > N — otherwise the flat path runs unchanged).  a2a_wire_dtype
    # compresses the inter-machine leg ("float8_e4m3fn"/"float8_e5m2",
    # comm/compress.py); None keeps the wire exact, which is what makes
    # the hierarchical path bit-compatible with the flat one.
    hier_a2a: bool = False
    a2a_wire_dtype: str | None = None

    def __post_init__(self):
        assert self.strategy in STRATEGIES, self.strategy
        assert self.comm_backend in ("xla", "pallas"), self.comm_backend
        if self.a2a_wire_dtype is not None:
            from ..comm.compress import WIRE_DTYPES
            assert self.a2a_wire_dtype in WIRE_DTYPES, self.a2a_wire_dtype

    def effective_batch_axes(
        self, mesh: jax.sharding.Mesh | None = None
    ) -> tuple[str, ...] | None:
        """Batch mesh axes with the CFG axis prepended (when present).

        The CFG pair is stacked on the batch dim by the sampler, so for
        sharding purposes it is just the major batch axis.  When a mesh is
        given, axes it does not carry are dropped — the same SPConfig then
        works on meshes with and without a 'cfg' axis.
        """
        axes = ((self.cfg_axis,) if self.cfg_axis else ()) + tuple(
            self.batch_axes or ())
        if mesh is not None:
            axes = tuple(a for a in axes if a in mesh.axis_names)
        return axes or None


def resolve_layout(
    cfg: SPConfig, mesh: jax.sharding.Mesh, num_q_heads: int, num_kv_heads: int
) -> GroupLayout:
    """Instantiate the paper's (P_u × P_r) plan for this mesh + head count."""
    sp = math.prod(mesh.shape[a] for a in cfg.sp_axes)
    n = mesh.shape[MACHINE_AXIS] if MACHINE_AXIS in cfg.sp_axes else 1
    m = sp // n

    def u_groups(p_u: int, outer: bool) -> int:
        # Hierarchical decomposition applies when the Ulysses groups span
        # the machine boundary with > 1 member per machine: u-blocks are
        # then machine-contiguous (block size (P_u/N)·P_r = M) and the
        # two-level factorisation u = u_hi·m_u + u_lo is exact.
        if (cfg.hier_a2a and outer and n > 1 and p_u > n
                and p_u % n == 0):
            return n
        return 1

    if cfg.strategy == "ring":
        return GroupLayout(cfg.sp_axes, 1, sp, ulysses_outer=True)
    if cfg.strategy == "ulysses":
        heads = num_q_heads if cfg.replicate_kv else math.gcd(num_q_heads, num_kv_heads)
        if heads % sp != 0:
            raise ValueError(
                f"ulysses needs SP ({sp}) | heads ({heads}); use usp/swift instead"
            )
        return GroupLayout(cfg.sp_axes, sp, 1, ulysses_outer=True,
                           u_groups=u_groups(sp, True))
    swift = cfg.strategy in ("swift", "swift_torus")
    pl = planner.plan(
        n, m, num_q_heads, num_kv_heads, swift=swift, replicate_kv=cfg.replicate_kv
    )
    return GroupLayout(cfg.sp_axes, pl.p_ulysses, pl.p_ring, ulysses_outer=swift,
                       u_groups=u_groups(pl.p_ulysses, swift))


def flash_attends(head_dim: int, window=None) -> bool:
    """Whether attention can run the Pallas flash kernel: compiled on a
    TPU (the platform test of ``compat.pallas_interpret``), for a
    head_dim of 64 (a [block, 64] tile spans the last dimension of the
    kernel's [B·H, L, D] operands) or of whole 128-lane tiles, and a
    static window (the kernel's mask is static)."""
    return (not pallas_interpret()
            and (head_dim == 64 or head_dim % 128 == 0)
            and (window is None or isinstance(window, int)))


def attention_lowering(cfg: SPConfig, mesh: jax.sharding.Mesh, q_len: int,
                       head_dim: int, window=None) -> str:
    """What ``sp_attention`` runs for self-attention of this shape.

    Below SP=2: ``"flash"``, the Pallas flash kernel
    (``kernels.ops.flash_attention``), on a one-device mesh for a real
    query length where ``flash_attends``; ``"reference"``, the
    materialised oracle, everywhere else: the CPU, decode's one-token
    queries, a head_dim or a traced window the kernel does not take,
    several devices (GSPMD partitions the oracle, and does not partition
    a Pallas call).  At SP > 1 the SP strategy's name and, after a
    ``+``, the attend inside each of its stages: ``flash`` where
    ``flash_attends`` (the kernel, the running (O', l, m) carried through
    it), ``reference`` elsewhere (``attend_partial``'s materialised score
    block), ``ring_flash`` under the Pallas comm backend (its fused
    ring-step kernel)."""
    flash = flash_attends(head_dim, window)
    if cfg.strategy != "full" and math.prod(mesh.shape[a]
                                            for a in cfg.sp_axes) > 1:
        stage = ("ring_flash" if cfg.comm_backend == "pallas"
                 else "flash" if flash else "reference")
        return f"{cfg.strategy}+{stage}"
    one_chip = math.prod(mesh.shape.values()) == 1 and q_len > 1
    return "flash" if one_chip and flash else "reference"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _one_chip_flash(q, k, v, scale, causal, window):
    """The flash kernel forward, the oracle's gradient: the Pallas call
    has no transpose rule, so training differentiates the materialised
    attention as it did before the kernel served SP=1."""
    return flash_attention(q, k, v, scale=scale, causal=causal, window=window)


def _one_chip_flash_fwd(q, k, v, scale, causal, window):
    return _one_chip_flash(q, k, v, scale, causal, window), (q, k, v)


def _one_chip_flash_bwd(scale, causal, window, qkv, g):
    mask = MaskSpec(causal=causal, window=window)
    _, vjp = jax.vjp(
        lambda q, k, v: reference_attention(q, k, v, scale=scale, mask=mask),
        *qkv)
    return vjp(g)


_one_chip_flash.defvjp(_one_chip_flash_fwd, _one_chip_flash_bwd)


def _usp_like(q, k, v, layout: GroupLayout, *, scale, causal, window,
              backend="xla", wire_dtype=None, flash=False):
    """Shared body for usp/swift/ulysses/ring: monolithic Ulysses gather →
    Ring Attention → scatter.  The layout decides which boundary each
    technique crosses (that single bit is the paper's §4.2 contribution).
    ``flash`` runs the ring's attends through the flash kernel."""
    ls = q.shape[1]
    g = gather_qkv(q, k, v, layout, backend=backend, wire_dtype=wire_dtype)
    b, lg, _, d = g.q.shape
    low = (FlashLowering.of(b, lg, g.k.shape[1], d, q.dtype) if flash
           else ORACLE)
    kpos_fn = lambda owner_r: group_positions(layout, ls, owner_r)
    part = ring_attention(
        low.q(g.q), low.kv(g.k), low.kv(g.v), layout,
        q_pos=low.q_pos(g.q_pos), k_pos_fn=kpos_fn,
        scale=scale, causal=causal, window=window, backend=backend,
        lowering=low,
    )
    return scatter_o(low.finalize(part, q.dtype), layout,
                     backend=backend, wire_dtype=wire_dtype)


def sp_attention(
    q: jax.Array,  # [B, L, Hq, D] global arrays (inside jit)
    k: jax.Array,  # [B, L, Hkv, D]
    v: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    cfg: SPConfig,
    scale: float | None = None,
    causal: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Distributed attention over the mesh per the configured SP strategy.

    Sequence is sharded over ``cfg.sp_axes`` (flat-rank order), batch over
    ``cfg.batch_axes``; heads/head_dim replicated inside the SP group.
    """
    lowering = attention_lowering(cfg, mesh, q.shape[1], q.shape[3], window)
    if lowering == "flash":
        return _one_chip_flash(q, k, v, scale, causal, window)
    if lowering == "reference":
        mask = MaskSpec(causal=causal, window=window)
        return reference_attention(q, k, v, scale=scale, mask=mask)

    layout = resolve_layout(cfg, mesh, q.shape[2], k.shape[2])
    if cfg.replicate_kv and layout.p_ulysses > 1:
        rep = layout.p_ulysses // math.gcd(layout.p_ulysses, k.shape[2])
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

    ba = cfg.effective_batch_axes(mesh)
    spec = P(ba, cfg.sp_axes, None, None)

    body = partial(
        torus_attention if cfg.strategy == "swift_torus" else _usp_like,
        layout=layout, scale=scale, causal=causal, window=window,
        backend=cfg.comm_backend, wire_dtype=cfg.a2a_wire_dtype,
        flash=lowering.endswith("+flash"))

    fn = jax.shard_map(
        lambda q, k, v: body(q, k, v),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
