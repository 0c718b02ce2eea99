"""Staged transfer programs over one-sided channels (DESIGN.md §8).

A ``Stream`` is the comm-side analogue of a CUDA/NVSHMEM stream: an
ordered sequence of channel stages making up one logical transfer program.
Each stage opens a channel (a fixed route), puts its tensors, and the
stage index is recorded so trace validation can reason about the program
shape.  The staged programs the SP schedules need are provided here:

  ring_shift          — one intra-ring rotation (Ring Attention's KV hop)
  torus_hop           — distance-k hop inside the Ulysses group (§4.3
                        stage k of the decomposed all-to-all)
  staged_all_to_all   — the full P_u-stage decomposition with the
                        stationary diagonal chunk (grouped_all_to_all)
  staged_ungroup      — its inverse (the Push-O / fourth all-to-all)
  intra_hop/inter_hop — the two legs of the hierarchical a2a: distance-j
                        rotation inside a machine sub-group / distance-k
                        rotation across machine sub-groups (§8.2)
  hier_all_to_all     — the two-level (intra-machine a2a, then staged
                        inter-machine hops) decomposition of the Ulysses
                        all-to-all; bit-identical output to the flat
                        path, optionally fp8 on the inter-machine wire
  hier_ungroup        — its inverse (the hierarchical Push-O)
  pipe_handoff        — the pipe-axis stage boundary transfer of the
                        displaced patch pipeline (models/dit.py)

Everything here is layout-agnostic: ``layout`` ducks as any object with
``axes``, ``p_ulysses``, ``my_coords()``, ``ring_perm(k)`` and
``ulysses_stage_perm(k)`` (core/collectives.GroupLayout in practice; the
duck-typing keeps this package import-free of core so core can build on
it without cycles).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from . import compress as _compress
from .channel import Channel, InFlight, shift_perm

__all__ = ["Stream", "ring_shift", "torus_hop", "intra_hop", "inter_hop",
           "staged_all_to_all", "staged_ungroup", "hier_all_to_all",
           "hier_ungroup", "pipe_handoff"]


@dataclasses.dataclass
class Stream:
    """An ordered program of channel transfers.

    ``channel`` mints a Channel bound to this stream at the current stage;
    ``next_stage`` advances the program counter.  Streams are trace-time
    bookkeeping only — they add no ops of their own.  ``backend`` selects
    the channel lowering for every stage of the program ("xla" | "pallas",
    see channel.py); ``interpret`` is the Pallas channels' CPU landing
    kernel mode (None follows the platform).
    """

    name: str
    stage: int = 0
    backend: str = "xla"
    interpret: bool | None = None

    def channel(self, axes, perm, label: str = "") -> Channel:
        return Channel(axes=tuple(axes), perm=tuple(perm),
                       name=f"{self.name}.{label}" if label else self.name,
                       stream=self.name, stage=self.stage,
                       backend=self.backend, interpret=self.interpret)

    def next_stage(self) -> int:
        self.stage += 1
        return self.stage

    # -- staged programs as stream methods (each advances the stage) ------
    def put(self, axes, perm, *tensors, label: str = "",
            overlaps: str = "") -> InFlight:
        fut = self.channel(axes, perm, label).put(*tensors, overlaps=overlaps)
        self.next_stage()
        return fut


def ring_shift(layout: Any, *tensors: jax.Array, shift: int = 1,
               stream: Stream | None = None,
               overlaps: str = "", backend: str = "xla",
               interpret: bool | None = None) -> InFlight:
    """One rotation inside each Ring group (same u): the KV hop of Ring
    Attention.  Returns the in-flight handle — the caller owns the wait."""
    stream = stream or Stream("ring", backend=backend, interpret=interpret)
    return stream.put(layout.axes, layout.ring_perm(shift), *tensors,
                      label=f"shift{shift}", overlaps=overlaps)


def torus_hop(layout: Any, k: int, *tensors: jax.Array,
              stream: Stream | None = None,
              overlaps: str = "", backend: str = "xla",
              interpret: bool | None = None) -> InFlight:
    """Distance-k hop inside each Ulysses group (same r): stage k of the
    §4.3 decomposed all-to-all."""
    stream = stream or Stream("torus", backend=backend, interpret=interpret)
    return stream.put(layout.axes, layout.ulysses_stage_perm(k), *tensors,
                      label=f"hop{k}", overlaps=overlaps)


def intra_hop(layout: Any, j: int, *tensors: jax.Array,
              stream: Stream | None = None,
              overlaps: str = "", backend: str = "xla",
              interpret: bool | None = None) -> InFlight:
    """Distance-j hop inside the machine-local Ulysses sub-group (same
    u_hi, same r): stage j of the hierarchical a2a's fast leg (§8.2).
    Never crosses the slow boundary."""
    stream = stream or Stream("hier", backend=backend, interpret=interpret)
    return stream.put(layout.axes, layout.ulysses_intra_stage_perm(j),
                      *tensors, label=f"intra{j}", overlaps=overlaps)


def inter_hop(layout: Any, k: int, *tensors: jax.Array,
              stream: Stream | None = None,
              overlaps: str = "", backend: str = "xla",
              interpret: bool | None = None) -> InFlight:
    """Distance-k hop across machine sub-groups (same u_lo, same r):
    stage k of the hierarchical a2a's slow leg — the only leg of the
    two-level program that touches the inter-machine wire."""
    stream = stream or Stream("hier", backend=backend, interpret=interpret)
    return stream.put(layout.axes, layout.ulysses_inter_stage_perm(k),
                      *tensors, label=f"inter{k}", overlaps=overlaps)


def _dyn_set(buf: jax.Array, idx, val: jax.Array) -> jax.Array:
    return lax.dynamic_update_slice_in_dim(buf, val[None], idx, axis=0)


def staged_all_to_all(
    x: jax.Array,
    layout: Any,
    *,
    split_axis: int,
    stream: Stream | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """All-to-all restricted to Ulysses groups, as P_u - 1 channel stages.

    Splits ``x`` into P_u chunks along ``split_axis``; chunk j is put to
    ulysses-peer j.  The diagonal chunk (j == my u) is stationary (§4.3)
    and never touches the wire.  Returns chunks stacked on a new leading
    axis in *source*-u order: ``out[j]`` = the chunk peer j produced for
    me.  Every stage's put is independent of every other stage's — the
    whole program can be in flight at once, which is what lets Torus
    interleave these stages with attention compute.
    """
    stream = stream or Stream("a2a", backend=backend, interpret=interpret)
    p_u = layout.p_ulysses
    chunks = jnp.stack(jnp.split(x, p_u, axis=split_axis), axis=0)
    if p_u == 1:
        return chunks
    u, _ = layout.my_coords()
    out = jnp.zeros_like(chunks)
    out = _dyn_set(out, u, jnp.take(chunks, u, axis=0))
    for k in range(1, p_u):
        # I put my chunk destined for peer (u + k); peer (u - k) puts mine.
        send = jnp.take(chunks, (u + k) % p_u, axis=0)
        recv = torus_hop(layout, k, send, stream=stream).wait()
        out = _dyn_set(out, (u - k) % p_u, recv)
    return out


def staged_ungroup(
    stacked: jax.Array,
    layout: Any,
    *,
    concat_axis: int,
    stream: Stream | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """Inverse program: put ``stacked[j]`` back to ulysses-peer j and
    concatenate the received chunks along ``concat_axis`` (the fourth
    all-to-all of Ulysses attention / Torus Push-O; diagonal stays put)."""
    stream = stream or Stream("a2a.inv", backend=backend, interpret=interpret)
    p_u = layout.p_ulysses
    if p_u == 1:
        return jnp.squeeze(stacked, axis=0)
    u, _ = layout.my_coords()
    out = jnp.zeros_like(stacked)
    out = _dyn_set(out, u, jnp.take(stacked, u, axis=0))
    for k in range(1, p_u):
        send = jnp.take(stacked, (u + k) % p_u, axis=0)
        recv = torus_hop(layout, k, send, stream=stream,
                         overlaps="next-layer compute").wait()
        out = _dyn_set(out, (u - k) % p_u, recv)
    return jnp.concatenate(list(out), axis=concat_axis)


def _hier_exchange(
    chunks: jax.Array,
    layout: Any,
    *,
    stream: Stream,
    wire_dtype: str | None = None,
    err: tuple | None = None,
    overlaps_inter: str = "peer inter hops + update fusions",
) -> jax.Array | tuple[jax.Array, tuple]:
    """Two-level routing core shared by hier_all_to_all / hier_ungroup.

    ``chunks`` is [P_u, ...] in destination-u order (chunk j is what I owe
    peer u = j); returns [P_u, ...] in source-u order (out[j] = what peer
    u = j produced for me) — the exact contract of the flat staged path.

    Factor u = u_hi * m_u + u_lo over (machine sub-group, local slot),
    g = layout.u_groups, m_u = P_u / g.  Two legs:

      fast leg (m_u - 1 intra stages): within each machine, local slot b
        sends the whole [g]-bundle of chunks destined for local slot
        (b + j) — after it, W[b'] holds the g chunks source (a, b')
        produced for the b-slots of every machine sub-group.
      slow leg (g - 1 inter stages): across machines, sub-group a sends
        the [m_u]-bundle W[:, (a + k) % g] — m_u chunks aggregated into
        one message, so the inter-machine wire sees g - 1 latency-paced
        stages instead of the flat path's P_u - 1.

    Both diagonals are stationary (the §4.3 observation, applied per
    level).  The program is pure routing — no arithmetic touches the
    payload — so the output is bit-identical to the flat path.  With
    ``wire_dtype`` the slow leg quantises each bundle (compress.py)
    before the put and dequantises on arrival; ``err`` (a tuple of g - 1
    fp32 buffers) enables error feedback, in which case the new residuals
    are returned alongside the output.
    """
    g = layout.u_groups
    p_u = layout.p_ulysses
    m_u = p_u // g
    rest = chunks.shape[1:]
    u, _ = layout.my_coords()
    a, b = u // m_u, u % m_u
    shaped = chunks.reshape((g, m_u) + rest)

    # fast leg: intra-machine exchange of dest-local-slot bundles
    w = jnp.zeros((m_u, g) + rest, chunks.dtype)
    w = _dyn_set(w, b, jnp.take(shaped, b, axis=1))
    for j in range(1, m_u):
        send = jnp.take(shaped, (b + j) % m_u, axis=1)
        recv = intra_hop(layout, j, send, stream=stream).wait()
        w = _dyn_set(w, (b - j) % m_u, recv)

    # slow leg: inter-machine exchange of per-sub-group bundles; every
    # stage is independent of every other, so the whole leg can be in
    # flight at once — the overlap declaration trace.validate checks
    out = jnp.zeros((g, m_u) + rest, chunks.dtype)
    out = _dyn_set(out, a, jnp.take(w, a, axis=1))
    new_err = []
    for k in range(1, g):
        send = jnp.take(w, (a + k) % g, axis=1)
        if wire_dtype is not None:
            if err is not None:
                wire, scale, e = _compress.ef_encode(
                    send, err[k - 1], wire_dtype)
                new_err.append(e)
            else:
                wire, scale = _compress.quantize(send, wire_dtype)
            rw, rs = inter_hop(layout, k, wire, scale, stream=stream,
                               overlaps=overlaps_inter).wait()
            recv = _compress.dequantize(rw, rs, chunks.dtype)
        else:
            recv = inter_hop(layout, k, send, stream=stream,
                             overlaps=overlaps_inter).wait()
        out = _dyn_set(out, (a - k) % g, recv)
    result = out.reshape((p_u,) + rest)
    if err is not None:
        return result, tuple(new_err)
    return result


def hier_all_to_all(
    x: jax.Array,
    layout: Any,
    *,
    split_axis: int,
    stream: Stream | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
    wire_dtype: str | None = None,
    err: tuple | None = None,
) -> jax.Array | tuple[jax.Array, tuple]:
    """Hierarchical two-level grouped all-to-all (§8.2): same contract as
    :func:`staged_all_to_all` — split into P_u chunks along ``split_axis``,
    deliver chunk j to ulysses-peer j, return received chunks stacked on a
    new leading axis in source-u order — but routed as an intra-machine
    a2a followed by g - 1 aggregated inter-machine hops."""
    stream = stream or Stream("hier.a2a", backend=backend,
                              interpret=interpret)
    p_u = layout.p_ulysses
    chunks = jnp.stack(jnp.split(x, p_u, axis=split_axis), axis=0)
    if p_u == 1:
        return chunks if err is None else (chunks, ())
    return _hier_exchange(chunks, layout, stream=stream,
                          wire_dtype=wire_dtype, err=err)


def hier_ungroup(
    stacked: jax.Array,
    layout: Any,
    *,
    concat_axis: int,
    stream: Stream | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
    wire_dtype: str | None = None,
    err: tuple | None = None,
) -> jax.Array | tuple[jax.Array, tuple]:
    """Hierarchical inverse (§8.2): same contract as
    :func:`staged_ungroup` — ``stacked[j]`` goes back to ulysses-peer j,
    received chunks concatenate along ``concat_axis``.  The exchange core
    is self-inverse (it is a transpose of the u coordinate), so this is
    the same two-leg program with a concat epilogue."""
    stream = stream or Stream("hier.a2a.inv", backend=backend,
                              interpret=interpret)
    p_u = layout.p_ulysses
    if p_u == 1:
        out = jnp.squeeze(stacked, axis=0)
        return out if err is None else (out, ())
    res = _hier_exchange(stacked, layout, stream=stream,
                         wire_dtype=wire_dtype, err=err,
                         overlaps_inter="next-layer compute")
    if err is not None:
        moved, new_err = res
        return jnp.concatenate(list(moved), axis=concat_axis), new_err
    return jnp.concatenate(list(res), axis=concat_axis)


def pipe_handoff(
    x: jax.Array,
    mesh: jax.sharding.Mesh,
    axis: str,
    *,
    shift: int = 1,
    batch_axes: tuple[str, ...] | None = None,
    stream: Stream | None = None,
    backend: str = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """Stage-boundary hand-off of the displaced patch pipeline: rotate the
    activation one stage forward along the pipe ``axis``.

    This is the transfer that replaces the GSPMD-implicit stage hand-off
    (ROADMAP item): an explicit collective-permute over the pipe axis
    carrying exactly the bytes the real pipeline moves per boundary, so
    (a) the HLO names the transfer and trace.py can validate that patch
    (p+1)'s hand-off overlaps patch p's stage compute, and (b) the
    emulation pays the wire cost it claims.  In the single-program
    emulation the activation is replicated over the pipe axis, so the
    rotation is value-preserving — the multi-device schedule it stands in
    for is documented in DESIGN.md §8.

    Must be called OUTSIDE any shard_map (it opens its own over ``axis``).
    """
    stream = stream or Stream("pipe", backend=backend, interpret=interpret)
    pp = mesh.shape[axis]
    if pp == 1:
        return x
    ch = stream.channel((axis,), shift_perm(pp, shift), f"handoff{stream.stage}")
    stream.next_stage()
    spec = P(batch_axes) if batch_axes else P()

    def body(xs):
        return ch.put(xs, overlaps="stage compute").wait()

    return jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(x)
