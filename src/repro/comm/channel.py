"""One-sided channel primitive (DESIGN.md §8): NVSHMEM put/signal/wait
semantics expressed in XLA terms.

The paper's runtime moves every tensor with one-sided NVSHMEM puts: the
sender writes straight into the receiver's buffer (no rendezvous), sets a
signal flag, and the receiver spin-waits on the flag only when it actually
needs the data — so the transfer rides a communication stream while SMs
keep computing.  On TPU-style backends the same three verbs map onto XLA
primitives:

    put     -> ``lax.ppermute``: lowered to collective-permute-start/done
               executed by the DMA engines; the latency-hiding scheduler
               hoists the start above independent compute, which is the
               moral equivalent of issuing the put on a comm stream.
               (The Pallas lowering is ``pltpu.make_async_remote_copy`` +
               ``rdma.start()``; this layer stays at the XLA level.)
    signal  -> the data dependency on the permute's result: XLA's done op
               plays the role of the flag write, so no separate flag
               tensor is materialised.
    wait    -> ``optimization_barrier``: pins *when* the received buffer
               may be consumed relative to other live values, without
               making the transfer itself depend on them — the receiver-
               side spin-wait, minus the spinning.

A ``Channel`` is a fixed (mesh axes, permutation) route — the double
buffer: every ``put`` returns an ``InFlight`` handle whose payload is the
receive buffer, and the caller decides when to ``wait`` on it.  Streams
(stream.py) compose channels into staged transfer programs; trace.py
records every put and validates the intended overlap against compiled HLO.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
from jax import lax

from . import profiler as _profiler
from . import trace as _trace

__all__ = ["Channel", "InFlight", "fence", "pin", "ring_perm_of",
           "shift_perm"]


def shift_perm(size: int, shift: int = 1) -> tuple[tuple[int, int], ...]:
    """Rotation permutation: rank r -> (r + shift) % size."""
    return tuple((r, (r + shift) % size) for r in range(size))


def ring_perm_of(layout: Any, shift: int = 1) -> tuple[tuple[int, int], ...]:
    """The layout's intra-ring rotation as a hashable perm table."""
    return tuple(layout.ring_perm(shift))


@dataclasses.dataclass(frozen=True)
class Channel:
    """A fixed one-sided route: ``put`` moves tensors one hop along
    ``perm`` over the named mesh ``axes``.

    Channels are cheap value objects — construct them per schedule stage;
    the name only matters for trace/debug output.  ``backend`` selects the
    lowering: ``"xla"`` (ppermute + optimization_barrier, overlap left to
    XLA's scheduler) or ``"pallas"`` (in-kernel DMA + explicit semaphores,
    DESIGN.md §8.1); ``interpret`` is the CPU landing kernel's
    interpreter mode (None follows the platform).
    """

    axes: tuple[str, ...]
    perm: tuple[tuple[int, int], ...]
    name: str = "chan"
    stream: str = ""  # owning Stream name (trace bookkeeping)
    stage: int = 0  # stage index within the stream program
    backend: str = "xla"  # "xla" | "pallas"
    interpret: bool | None = None  # CPU landing kernel; None = platform

    def __post_init__(self):
        assert self.backend in ("xla", "pallas"), self.backend

    def put(self, *tensors: jax.Array, overlaps: str = "") -> "InFlight":
        """Issue the one-sided transfer of ``tensors`` (start the DMA).

        Multiple tensors ride the same route in one put (K and V travel
        together).  ``overlaps`` names the compute this transfer is meant
        to hide behind; trace validation asserts the compiled HLO admits
        it.  The returned handle's payload is the *received* buffer — in
        SPMD every rank is simultaneously the sender and the receiver of
        its neighbour's put.
        """
        if self.backend == "pallas":
            return self._put_pallas(tensors, overlaps)
        meta = self._leg_meta(tensors, overlaps, "xla")
        if meta is not None:
            _profiler.mark(_profiler.active(), meta, "issue", tensors)
        perm = list(self.perm)
        out = tuple(lax.ppermute(t, self.axes, perm=perm) for t in tensors)
        _trace.emit(_trace.TransferEvent(
            stream=self.stream, channel=self.name, stage=self.stage,
            axes=tuple(self.axes), perm=tuple(self.perm),
            shape=tuple(tensors[0].shape), n_tensors=len(tensors),
            overlaps=overlaps, backend="xla"))
        if meta is not None:
            _profiler.mark(_profiler.active(), meta, "signal", out)
        return InFlight(channel=self, payload=out, meta=meta)

    def _leg_meta(self, tensors: tuple[jax.Array, ...], overlaps: str,
                  backend: str) -> Any:
        """Mint the runtime-profiler leg identity for one put, or None
        when no profiler is active at trace time (zero-cost default)."""
        prof = _profiler.active()
        if prof is None:
            return None
        return prof.new_leg(
            kind="comm", stream=self.stream, channel=self.name,
            stage=self.stage, axes=tuple(self.axes),
            nbytes=_profiler.nbytes_of(tensors), n_tensors=len(tensors),
            backend=backend, intent=overlaps)

    def _put_pallas(self, tensors: tuple[jax.Array, ...],
                    overlaps: str) -> "InFlight":
        """Pallas lowering: semaphore-tracked delivery (DESIGN.md §8.1)."""
        from . import pallas_backend as _pb

        sem = _pb.new_sem(self.name, self.stage)
        meta = self._leg_meta(tensors, overlaps, "pallas")
        if meta is not None:
            _profiler.mark(_profiler.active(), meta, "issue", tensors)
        _trace.emit(_trace.TransferEvent(
            stream=self.stream, channel=self.name, stage=self.stage,
            axes=tuple(self.axes), perm=tuple(self.perm),
            shape=tuple(tensors[0].shape), n_tensors=len(tensors),
            overlaps=overlaps, backend="pallas"))
        _trace.emit_sem(_trace.SemEvent(
            kind="put", sem=sem, stream=self.stream, channel=self.name,
            stage=self.stage))
        out = _pb.deliver(tensors, tuple(self.axes), tuple(self.perm),
                          interpret=self.interpret, profile_src=self)
        _trace.emit_sem(_trace.SemEvent(
            kind="signal", sem=sem, stream=self.stream, channel=self.name,
            stage=self.stage))
        if meta is not None:
            # the DMA-semaphore signal: fires once landing_copy delivered
            _profiler.mark(_profiler.active(), meta, "signal", out)
        return InFlight(channel=self, payload=out, sem=sem, meta=meta)

    def put_fused(self, *tensors: jax.Array, overlaps: str = "") -> "InFlight":
        """Deliver a put that was ISSUED inside a fused kernel
        (kernels/ring_flash.py): the kernel already started the copy at
        its first grid step and waited it only after its last compute
        block; ``tensors`` are the forwarded buffers it produced.  This
        records the schedule (put flagged ``overlap=True`` — the
        semaphore validator then requires compute between issue and
        wait) and performs the wire move: the kernel's DMA stages the
        chunk into the forward buffer on the *local* device, and the
        inter-device hop is ``pallas_backend.deliver`` — the remote copy
        on a TPU, ppermute plus landing kernel elsewhere (DESIGN.md §8.1).
        """
        assert self.backend == "pallas", "put_fused is a Pallas-path verb"
        from . import pallas_backend as _pb

        meta = self._leg_meta(tensors, overlaps, "pallas")
        if meta is not None:
            _profiler.mark(_profiler.active(), meta, "issue", tensors)
        sem = _pb.fused_transfer_events(
            self, tuple(tensors[0].shape), len(tensors), overlaps=overlaps)
        out = _pb.deliver(tensors, tuple(self.axes), tuple(self.perm),
                          interpret=self.interpret)
        _trace.emit_sem(_trace.SemEvent(
            kind="signal", sem=sem, stream=self.stream, channel=self.name,
            stage=self.stage))
        if meta is not None:
            _profiler.mark(_profiler.active(), meta, "signal", out)
        return InFlight(channel=self, payload=out, sem=sem, meta=meta)


@dataclasses.dataclass(frozen=True)
class InFlight:
    """Handle to a put in flight; ``payload`` is the receive buffer."""

    channel: Channel
    payload: tuple[jax.Array, ...]
    sem: str = ""  # semaphore id (Pallas backend only)
    meta: Any = None  # runtime-profiler leg identity (profiling only)

    def wait(self, *deps: jax.Array) -> Any:
        """Signal-wait: deliver the buffer, ordered after ``deps``.

        With no deps this is a plain delivery (the data dependency is the
        signal).  With deps, the received tensors and the deps are fenced
        together so the consumer cannot be scheduled before the deps
        finish — while the transfer start stays independent and hoistable.
        Returns the payload (unpacked when it is a single tensor); with
        deps, returns ``(payload..., deps...)`` all fenced.
        """
        if self.sem:
            _trace.emit_sem(_trace.SemEvent(
                kind="wait", sem=self.sem, stream=self.channel.stream,
                channel=self.channel.name, stage=self.channel.stage))
        if self.meta is not None and _profiler.active() is not None:
            # fires when the receiver's independent compute (the deps) is
            # done and it truly needs the buffer; with no deps the wait
            # is observed at delivery (exposure reads as zero)
            _profiler.mark(_profiler.active(), self.meta, "wait",
                           deps if deps else self.payload)
        if not deps:
            return self.payload[0] if len(self.payload) == 1 else self.payload
        vals, deps_out = fence(self.payload, deps)
        if len(vals) == 1:
            return (vals[0], *deps_out)
        return (*vals, *deps_out)


def fence(tensors: Sequence[jax.Array],
          deps: Sequence[jax.Array]) -> tuple[tuple, tuple]:
    """Joint ordering point: gate ``tensors`` (received or resident
    buffers) on ``deps`` so compute consuming them cannot start before the
    deps complete — the consumer-side wait of the signal protocol.  Values
    that do not pass through the fence (e.g. the next put) stay
    independent and keep overlapping.  Returns (tensors, deps) pinned.
    """
    out = lax.optimization_barrier(tuple(tensors) + tuple(deps))
    n = len(tuple(tensors))
    return out[:n], out[n:]


def pin(xs: Sequence[jax.Array]) -> tuple:
    """Serialise a value chain (e.g. an accumulator) across schedule steps
    so only O(1) intermediates are live — the quiet counterpart of fence.
    """
    return lax.optimization_barrier(tuple(xs))
