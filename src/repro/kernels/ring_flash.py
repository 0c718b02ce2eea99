"""Fused ring-step kernel: flash_mqkv + the next KV-chunk put issued
in-kernel (the paper's Algorithm-2 overlap, DESIGN.md §8.1).

``flash_mqkv`` computes one ring step's attention; the transfer of the KV
chunk to the next ring rank is then a separate op whose overlap with the
attention compute is left to XLA's latency-hiding scheduler.  This kernel
closes that gap the way the paper's NVSHMEM kernels do: the *same* kernel
that consumes the current KV chunk also issues its forwarding copy —

  * at the **first grid step**, before any compute, the DMA of the whole
    (K, V) chunk into the forward buffers is started
    (``pltpu.make_async_copy`` — a *local* copy into the RDMA staging
    buffer; the inter-device hop itself is ``Channel.put_fused``, which
    hands the staged buffers to ``pallas_backend.deliver``);
  * every (q-block, kv-block) grid step runs the unchanged flash_mqkv
    online-softmax body while the copy rides the DMA engines;
  * only at the **last grid step**, after the final output write, does the
    kernel wait the DMA semaphores — the no-blocking-wait schedule
    ``comm.trace.validate_semaphores`` checks.

The attention math is byte-for-byte flash_mqkv's (its kernel body is
invoked on the same refs), so (o, l, m) parity with ``flash_mqkv`` is
structural; the property tests in tests/test_ring_flash.py lock it in.
The forwarded buffers are returned to the caller; ``core/ring.py`` hands
them to ``Channel.put_fused`` for the wire move (a remote copy on a TPU,
emulated with ppermute on CPU — see DESIGN.md §8.1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import pallas_interpret
from .flash_mqkv import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, _kernel as _flash_body


def _ring_kernel(
    q_ref, k_ref, v_ref, qp_ref, kp_ref, oin_ref, lin_ref, min_ref,
    kfull_ref, vfull_ref,
    o_ref, l_ref, m_ref, kfwd_ref, vfwd_ref,
    acc_s, m_s, l_s, sem,
    *, scale: float, causal: bool, window: int | None, finalize: bool,
    n_k: int, has_state: bool,
):
    h, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k_dma = pltpu.make_async_copy(kfull_ref, kfwd_ref, sem.at[0])
    v_dma = pltpu.make_async_copy(vfull_ref, vfwd_ref, sem.at[1])

    # issue the forwarding put before any compute (Algorithm 1: pull next,
    # compute current — expressed in push form)
    @pl.when((h == 0) & (qi == 0) & (ki == 0))
    def _issue():
        k_dma.start()
        v_dma.start()

    _flash_body(
        q_ref, k_ref, v_ref, qp_ref, kp_ref, oin_ref, lin_ref, min_ref,
        o_ref, l_ref, m_ref, acc_s, m_s, l_s,
        scale=scale, causal=causal, window=window, finalize=finalize,
        n_k=n_k, has_state=has_state,
    )

    # wait only after the LAST compute block of the whole grid
    last_h = pl.num_programs(0) - 1
    last_q = pl.num_programs(1) - 1

    @pl.when((h == last_h) & (qi == last_q) & (ki == n_k - 1))
    def _drain():
        k_dma.wait()
        v_dma.wait()


def ring_flash_step(
    q: jax.Array,  # [BH, Lq, D]
    k: jax.Array,  # [BHkv, Lk, D]
    v: jax.Array,
    q_pos: jax.Array,  # [Lq] int32
    k_pos: jax.Array,  # [Lk] int32, -1 = padding
    *,
    group: int = 1,
    scale: float | None = None,
    causal: bool = False,
    window: int | None = None,
    state: tuple[jax.Array, jax.Array, jax.Array] | None = None,
    finalize: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool | None = None,
):
    """One fused ring step.  Same contract as ``flash_mqkv`` plus the
    forwarded chunk: returns ``(o, l, m), (k_fwd, v_fwd)`` where the
    forward buffers hold the consumed KV chunk, copied by the in-kernel
    DMA that overlapped the attention compute."""
    bh, lq, d = q.shape
    bhkv, lk, _ = k.shape
    assert bh == bhkv * group, (bh, bhkv, group)
    assert lq % block_q == 0 and lk % block_k == 0, (lq, lk, block_q, block_k)
    if scale is None:
        scale = d ** -0.5
    n_q, n_k = lq // block_q, lk // block_k
    has_state = state is not None

    qp2 = q_pos.reshape(lq, 1)  # a column, as flash_mqkv takes it
    kp2 = k_pos.reshape(1, lk)
    if state is None:
        o_in = jnp.zeros((bh, block_q, d), jnp.float32)
        l_in = jnp.zeros((bh, block_q, 1), jnp.float32)
        m_in = jnp.zeros((bh, block_q, 1), jnp.float32)
        oin_spec = pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, 0, 0))
        lin_spec = pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, 0, 0))
    else:
        o_in, l_in, m_in = state
        l_in, m_in = l_in[..., None], m_in[..., None]
        oin_spec = pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, qi, 0))
        lin_spec = pl.BlockSpec((None, block_q, 1),
                                lambda h, qi, ki: (h, qi, 0))

    kernel = functools.partial(
        _ring_kernel, scale=scale, causal=causal, window=window,
        finalize=finalize, n_k=n_k, has_state=has_state,
    )
    out_shape = (
        jax.ShapeDtypeStruct((bh, lq, d), q.dtype if finalize else jnp.float32),
        jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    )
    o, l, m, k_fwd, v_fwd = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda h, qi, ki, g=group: (h // g, ki, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda h, qi, ki, g=group: (h // g, ki, 0)),
            pl.BlockSpec((block_q, 1), lambda h, qi, ki: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda h, qi, ki: (0, ki)),
            oin_spec,
            lin_spec,
            lin_spec,
            pl.BlockSpec(memory_space=pl.ANY),  # DMA source: full K
            pl.BlockSpec(memory_space=pl.ANY),  # DMA source: full V
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # forward buffer: K
            pl.BlockSpec(memory_space=pl.ANY),  # forward buffer: V
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            # DMA issue/drain at fixed grid steps imposes an execution
            # order; no parallel dimension semantics for the fused kernel
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=pallas_interpret(interpret),
    )(q, k, v, qp2, kp2, o_in, l_in, m_in, k, v)
    return (o, l[..., 0], m[..., 0]), (k_fwd, v_fwd)
