"""Pallas lowering of the one-sided channel verbs (DESIGN.md §8.1).

The XLA backend (channel.py) leaves the put's overlap to the latency-hiding
scheduler; this backend issues the transfer *itself*, the way the paper's
NVSHMEM kernels do, with explicit semaphores:

    put     -> ``pltpu.make_async_remote_copy(...).start()``: the RDMA is
               started from inside a Pallas kernel, on the DMA engines,
               while the kernel's compute continues.
    signal  -> the copy's recv semaphore (``pltpu.SemaphoreType.DMA``):
               signalled by hardware when the payload has landed — the
               NVSHMEM signal flag, with no flag tensor materialised.
    wait    -> ``dma.wait()`` (``pltpu.semaphore_wait`` on the recv
               semaphore): the receiver-side spin-wait, executed as late
               as the schedule allows.

Two lowering branches, selected by the platform JAX runs on:

  * **TPU**: a kernel performs the remote copy proper.  The destination
    rank comes from the channel's perm table indexed by ``lax.axis_index``
    over the route's axes — a *distance*, exactly like the XLA route — and
    is handed to ``make_async_remote_copy`` as a mesh coordinate over
    those axes (one axis or several), so every route lowers this way.
    Before any byte moves, each rank tells the rank that writes into it
    that its receive buffers are live (barrier semaphore), and writes only
    after its own destination has said the same.  There is no emulation
    on a TPU: a route this branch cannot express raises.
  * **CPU** (the tested path): inter-device wire movement is not
    expressible inside an interpret-mode kernel, so the wire move stays a
    ``lax.ppermute`` (same HLO pairs, so `trace.validate` keeps working
    unchanged) and a *landing kernel* executes the put/signal/wait
    protocol on the received buffer: an in-kernel async copy
    (``pltpu.make_async_copy`` + DMA semaphore) delivers the payload into
    the receive buffer.  Everything downstream of the channel — the fused
    ring kernel, the semaphore schedule, trace validation — runs for real.

Every protocol step is recorded as a ``trace.SemEvent`` so commcheck can
validate the schedule's well-formedness (pairing, no wait-before-put, no
blocking wait) next to the HLO-level overlap checks.
"""
from __future__ import annotations

import functools
import itertools
import zlib
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import pallas_interpret
from . import profiler as _profiler
from . import trace as _trace

__all__ = ["BACKENDS", "deliver", "fused_transfer_events", "new_sem",
           "landing_copy"]

BACKENDS = ("xla", "pallas")

_sem_counter = itertools.count()
# barrier-semaphore ids the TPU remote put hashes its routes into
_COLLECTIVE_IDS = 64


def new_sem(channel_name: str, stage: int) -> str:
    """Mint a unique semaphore id for one put (trace bookkeeping only —
    the runtime semaphore is a kernel scratch, not addressed by name)."""
    return f"{channel_name}.s{stage}#{next(_sem_counter)}"


def _landing_kernel(*refs):
    """Deliver ``n`` received buffers through in-kernel async copies.

    refs = (in_0..in_{n-1}, out_0..out_{n-1}, sem_0..sem_{n-1}).  All
    copies are started before any is waited — the multi-tensor put (K and
    V ride one route) stays a single protocol step.
    """
    n = len(refs) // 3
    ins, outs, sems = refs[:n], refs[n:2 * n], refs[2 * n:]
    dmas = [pltpu.make_async_copy(i, o, s)
            for i, o, s in zip(ins, outs, sems)]
    for dma in dmas:
        dma.start()
    for dma in dmas:
        dma.wait()


def landing_copy(tensors: Sequence[jax.Array], *,
                 interpret: bool | None = None) -> tuple[jax.Array, ...]:
    """Run the landing kernel over ``tensors``.

    One ``pallas_call`` delivers all tensors of a put: the buffers stay in
    ANY/HBM space (no VMEM staging of arbitrarily-shaped payloads) and one
    DMA semaphore per tensor tracks completion.
    """
    tensors = tuple(tensors)
    n = len(tensors)
    out = pl.pallas_call(
        _landing_kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tensors],
        scratch_shapes=[pltpu.SemaphoreType.DMA] * n,
        interpret=pallas_interpret(interpret),
    )(*tensors)
    return tuple(out)


def _route_tables(perm: Sequence[tuple[int, int]],
                  size: int) -> tuple[list[int], list[int]]:
    """(destination, source) of every rank along a route that must be a
    full permutation of ``range(size)``: a rank that received nothing
    would keep an unwritten buffer, where ``lax.ppermute`` gives zeros."""
    dst, src = [-1] * size, [-1] * size
    for s, d in perm:
        dst[s], src[d] = d, s
    if -1 in dst or -1 in src:
        raise ValueError(
            f"the TPU remote put needs a full permutation of {size} ranks; "
            f"got {sorted(perm)}")
    return dst, src


def _remote_put_kernel(ids_ref, *refs, axes: tuple[str, ...]):
    """TPU branch: remote-copy every tensor to rank ``ids[0]`` of the
    route, receiving from rank ``ids[1]`` (scalar prefetch).

    refs = (in_0.., out_0.., send_sem_0.., recv_sem_0..).  The out refs
    are this device's *receive* buffers — written by the neighbour's
    symmetric copy, exactly NVSHMEM's symmetric-heap contract.  The
    barrier handshake keeps a rank from writing into a neighbour that has
    not yet entered the kernel (whose receive buffer may still hold a
    live value of an earlier op).
    """
    n = len(refs) // 4
    ins, outs = refs[:n], refs[n:2 * n]
    send, recv = refs[2 * n:3 * n], refs[3 * n:]
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(barrier, 1, device_id={axes: ids_ref[1]},
                           device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 1)
    dmas = [
        pltpu.make_async_remote_copy(
            src_ref=i, dst_ref=o, send_sem=s, recv_sem=r,
            device_id={axes: ids_ref[0]},
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        for i, o, s, r in zip(ins, outs, send, recv)
    ]
    for dma in dmas:
        dma.start()
    for dma in dmas:
        dma.wait()


def _tpu_remote_put(tensors: tuple[jax.Array, ...], axes: tuple[str, ...],
                    perm: Sequence[tuple[int, int]]) -> tuple[jax.Array, ...]:
    """In-kernel one-sided put along the route (compiled for a TPU; call
    inside ``shard_map`` over a mesh that carries ``axes``)."""
    n = len(tensors)
    axes = tuple(axes)
    dst, src = _route_tables(perm, lax.axis_size(axes))
    me = lax.axis_index(axes)
    ids = jnp.stack([jnp.asarray(dst, jnp.int32)[me],
                     jnp.asarray(src, jnp.int32)[me]])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
        scratch_shapes=([pltpu.SemaphoreType.DMA] * (2 * n)),
    )
    out = pl.pallas_call(
        functools.partial(_remote_put_kernel, axes=axes),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tensors],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            # kernels with different routes must not share a barrier
            # semaphore (a signal meant for one would release the other)
            collective_id=_collective_id(axes, perm)),
    )(ids, *tensors)
    return tuple(out)


def _collective_id(axes: tuple[str, ...],
                   perm: Sequence[tuple[int, int]]) -> int:
    """Barrier-semaphore id of a route: equal routes share one, distinct
    routes get distinct ids (up to a hash collision)."""
    key = repr((tuple(axes), tuple(sorted(perm)))).encode()
    return zlib.crc32(key) % _COLLECTIVE_IDS


def deliver(
    tensors: Sequence[jax.Array],
    axes: tuple[str, ...],
    perm: Sequence[tuple[int, int]],
    *,
    interpret: bool | None = None,
    profile_src=None,
) -> tuple[jax.Array, ...]:
    """Move ``tensors`` one hop along the channel route, Pallas-lowered.

    The caller (Channel.put) owns the trace events; this function owns the
    lowering branch choice: the remote copy on a TPU, the ppermute plus
    landing kernel elsewhere (``interpret`` applies to that kernel).
    ``profile_src`` (the owning Channel, when a runtime profiler is
    active) brackets the landing kernel's DMA semaphore wait as its own
    span — the protocol cost on top of the wire move (DESIGN.md §12).
    """
    tensors = tuple(tensors)
    if jax.default_backend() == "tpu":
        return _tpu_remote_put(tensors, tuple(axes), perm)
    # emulation branch: ppermute carries the bytes (keeping the HLO route
    # validatable), the landing kernel executes the semaphore protocol
    moved = tuple(lax.ppermute(t, axes, perm=list(perm)) for t in tensors)
    prof = _profiler.active()
    meta = None
    if prof is not None and profile_src is not None:
        meta = prof.new_leg(
            kind="comm", stream=profile_src.stream,
            channel=f"{profile_src.name}.semwait", stage=profile_src.stage,
            axes=tuple(axes), nbytes=_profiler.nbytes_of(tensors),
            n_tensors=len(tensors), backend="pallas", intent="sem")
        _profiler.mark(prof, meta, "issue", moved)
    out = landing_copy(moved, interpret=interpret)
    if meta is not None:
        _profiler.mark(prof, meta, "signal", out)
    return out


def fused_transfer_events(
    channel,
    shape: tuple[int, ...],
    n_tensors: int,
    *,
    overlaps: str,
) -> str:
    """Record the schedule of an *in-kernel* fused put (ring_flash.py):
    the kernel issues the copy at its first grid step and waits only after
    its last compute block, so the event sequence is put → signal at
    completion; the matching SemEvent('wait') is emitted by InFlight.wait
    and the kernel wrapper contributes the 'compute' markers in between.
    Returns the minted semaphore id.
    """
    sem = new_sem(channel.name, channel.stage)
    _trace.emit(_trace.TransferEvent(
        stream=channel.stream, channel=channel.name, stage=channel.stage,
        axes=tuple(channel.axes), perm=tuple(channel.perm),
        shape=tuple(shape), n_tensors=n_tensors,
        overlaps=overlaps, backend="pallas"))
    _trace.emit_sem(_trace.SemEvent(
        kind="put", sem=sem, stream=channel.stream, channel=channel.name,
        stage=channel.stage, overlap=True))
    return sem
