"""Mixture-of-Experts layer with expert-parallel all-to-all dispatch.

Experts are sharded over the 'model' mesh axis (expert parallelism).  For
sequence-sharded activations (train/prefill) tokens are routed with a
sort-based, capacity-dropped dispatch and exchanged with their expert
owners via ``lax.all_to_all`` over 'model' — the same all-to-all family the
paper's Ulysses path optimises, so the MoE dispatch shows up in the
roofline collective term alongside attention.

For decode (activations replicated over 'model') no all-to-all is needed:
each shard computes its local experts' contribution and a ``psum``
combines — the standard inference EP schedule.

Routing: softmax top-k, optional shared experts (qwen2-moe) and a dense
residual branch (arctic) are handled by the caller (models/registry).  A
GShard-style load-balance auxiliary loss is returned.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .blocks import ParallelContext, ParamBuilder, Params


def init_moe(b: ParamBuilder, cfg, prefix: str = "moe", n_pad_experts: int = 0) -> None:
    m = cfg.moe
    d, ff = cfg.d_model, m.moe_d_ff
    e = m.n_experts + n_pad_experts
    b.add(f"{prefix}/router/w", (d, m.n_experts), ("embed", None))
    b.add(f"{prefix}/wi_gate", (e, d, ff), ("experts", "embed", "expert_mlp"))
    b.add(f"{prefix}/wi_up", (e, d, ff), ("experts", "embed", "expert_mlp"))
    b.add(f"{prefix}/wo", (e, ff, d), ("experts", "expert_mlp", "embed"),
          scale=ff ** -0.5 / (2 * cfg.n_layers) ** 0.5)


def padded_n_experts(cfg, ep_degree: int) -> int:
    """Experts padded up so the expert dim divides the EP axis (e.g. qwen2's
    60 experts on a 16-way axis -> 64, last 4 never routed to)."""
    e = cfg.moe.n_experts
    return int(math.ceil(e / ep_degree) * ep_degree)


def _positions_within_group(ids: jax.Array, n_groups: int) -> jax.Array:
    """Stable rank of each element within its id-group (sort-based; the
    XLA-friendly alternative to a [T, E, C] one-hot dispatch tensor)."""
    t = ids.shape[0]
    perm = jnp.argsort(ids, stable=True)
    sorted_ids = ids[perm]
    starts = jnp.searchsorted(sorted_ids, jnp.arange(n_groups), side="left")
    pos_sorted = jnp.arange(t) - starts[sorted_ids]
    return jnp.zeros(t, jnp.int32).at[perm].set(pos_sorted)


def _expert_ffn(x: jax.Array, wg: jax.Array, wu: jax.Array, wo: jax.Array,
                act: str) -> jax.Array:
    """Batched expert FFN: x [E, C, d] with per-expert weights [E, d, ff]."""
    if act in ("swiglu", "geglu"):
        gate = jnp.einsum("ecd,edf->ecf", x, wg.astype(x.dtype))
        gate = jax.nn.silu(gate) if act == "swiglu" else jax.nn.gelu(gate)
        h = gate * jnp.einsum("ecd,edf->ecf", x, wu.astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, wu.astype(x.dtype)))
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(x.dtype))


def _route(x2d: jax.Array, router_w: jax.Array, top_k: int, n_real: int):
    """Returns (topk ids [T,k], weights [T,k], aux load-balance loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # GShard aux: E * sum_e f_e * p_e
    f = jnp.mean(jnp.sum(jax.nn.one_hot(ids, n_real), axis=1), axis=0)
    p = jnp.mean(probs, axis=0)
    aux = n_real * jnp.sum(f * p)
    return ids, w.astype(x2d.dtype), aux


def _moe_local(x, router_w, wg, wu, wo, *, cfg, ep_axes, ep_degree, replicated):
    """Per-device MoE body inside shard_map.

    x: [T_local, d].  wg/wu/wo: [E_local, ...] (this device's experts).
    """
    m = cfg.moe
    t_l, d = x.shape
    e_local = wg.shape[0]
    ids, w, aux = _route(x, router_w, m.top_k, m.n_experts)

    if replicated:
        # decode: everyone has all tokens; compute my experts, psum outputs.
        my_rank = lax.axis_index(ep_axes)
        lo = my_rank * e_local
        flat_ids = ids.reshape(-1)
        local = flat_ids - lo
        keep = (local >= 0) & (local < e_local)
        cap = t_l * m.top_k  # worst case, tiny in decode
        pos = _positions_within_group(jnp.where(keep, local, e_local), e_local + 1)
        src = jnp.repeat(jnp.arange(t_l), m.top_k)
        buf = jnp.zeros((e_local, cap, d), x.dtype)
        buf = buf.at[jnp.where(keep, local, e_local), pos].set(x[src], mode="drop")
        out_buf = _expert_ffn(buf, wg, wu, wo, cfg.act)
        gathered = out_buf.at[jnp.where(keep, local, e_local), pos].get(
            mode="fill", fill_value=0.0)
        y = jnp.zeros((t_l, d), x.dtype)
        y = y.at[src].add(gathered * w.reshape(-1)[:, None])
        y = lax.psum(y, ep_axes)
        return y, aux

    # --- expert-parallel all-to-all dispatch (train / prefill) -----------
    flat_ids = ids.reshape(-1)  # [T*k]
    src = jnp.repeat(jnp.arange(t_l), m.top_k)
    peer = flat_ids // e_local  # owner of each slot's expert
    cap_send = int(math.ceil(t_l * m.top_k / ep_degree * m.capacity_factor))
    pos = _positions_within_group(peer, ep_degree)  # slot within peer buffer
    in_cap = pos < cap_send

    send_x = jnp.zeros((ep_degree, cap_send, d), x.dtype)
    send_x = send_x.at[peer, pos].set(
        jnp.where(in_cap[:, None], x[src], 0.0), mode="drop")
    send_eid = jnp.full((ep_degree, cap_send), -1, jnp.int32)
    send_eid = send_eid.at[peer, pos].set(
        jnp.where(in_cap, flat_ids % e_local, -1), mode="drop")

    recv_x = lax.all_to_all(send_x, ep_axes, 0, 0, tiled=True)
    recv_eid = lax.all_to_all(send_eid, ep_axes, 0, 0, tiled=True)

    rx = recv_x.reshape(ep_degree * cap_send, d)
    reid = recv_eid.reshape(-1)
    valid = reid >= 0
    cap_e = int(math.ceil(ep_degree * cap_send / e_local * m.capacity_factor))
    eid_or_pad = jnp.where(valid, reid, e_local)
    epos = _positions_within_group(eid_or_pad, e_local + 1)
    buf = jnp.zeros((e_local, cap_e, d), x.dtype)
    buf = buf.at[eid_or_pad, epos].set(jnp.where(valid[:, None], rx, 0.0),
                                       mode="drop")
    out_buf = _expert_ffn(buf, wg, wu, wo, cfg.act)
    out_tok = out_buf.at[eid_or_pad, epos].get(mode="fill", fill_value=0.0)
    out_tok = jnp.where(valid[:, None], out_tok, 0.0)

    back = lax.all_to_all(out_tok.reshape(ep_degree, cap_send, d),
                          ep_axes, 0, 0, tiled=True)
    gathered = back.at[peer, pos].get(mode="fill", fill_value=0.0)
    gathered = jnp.where(in_cap[:, None], gathered, 0.0)
    y = jnp.zeros((t_l, d), x.dtype)
    y = y.at[src].add(gathered * w.reshape(-1)[:, None])
    return y, aux


def _moe_token_gather_decode(x2d, rw, wg, wu, wo, *, cfg, ep_axes, e_local,
                             ff_axes, batch_axes):
    """Decode-mode EP with FSDP'd expert weights — beyond-paper (§Perf).

    The naive decode path all-gathers the expert hidden dims (sharded over
    'data' for arctic-class models) every step: ~GBs of weights per token.
    Instead gather the TOKENS over 'data' (KBs), compute each rank's ff
    slice, psum the partial outputs, and slice the local batch back —
    weights never move.
    """
    t_l, d = x2d.shape
    m = cfg.moe
    x_all = lax.all_gather(x2d, batch_axes, axis=0, tiled=True)  # [T_all, d]
    t_all = x_all.shape[0]
    ids, w, aux = _route(x_all, rw, m.top_k, m.n_experts)
    my_rank = lax.axis_index(ep_axes)
    lo = my_rank * e_local
    flat_ids = ids.reshape(-1)
    local = flat_ids - lo
    keep = (local >= 0) & (local < e_local)
    cap = t_all * m.top_k
    pos = _positions_within_group(jnp.where(keep, local, e_local), e_local + 1)
    src = jnp.repeat(jnp.arange(t_all), m.top_k)
    buf = jnp.zeros((e_local, cap, d), x2d.dtype)
    buf = buf.at[jnp.where(keep, local, e_local), pos].set(x_all[src], mode="drop")
    out_buf = _expert_ffn(buf, wg, wu, wo, cfg.act)  # ff dim is a slice
    gathered = out_buf.at[jnp.where(keep, local, e_local), pos].get(
        mode="fill", fill_value=0.0)
    y = jnp.zeros((t_all, d), x2d.dtype)
    y = y.at[src].add(gathered * w.reshape(-1)[:, None])
    # partial over both expert shards (model) and ff slices (data)
    y = lax.psum(y, ep_axes + ff_axes)
    my_b = lax.axis_index(batch_axes)
    y = lax.dynamic_slice_in_dim(y, my_b * t_l, t_l, axis=0)
    return y, aux


def moe_block(
    x: jax.Array,  # [B, L, d]
    p: Params,  # {'router': {'w'}, 'wi_gate', 'wi_up', 'wo'} (padded E)
    cfg,
    ctx: ParallelContext,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y [B, L, d], aux loss scalar)."""
    ep_axes = ("model",)
    mesh = ctx.mesh
    ep_degree = math.prod(mesh.shape[a] for a in ep_axes)
    ba = ctx.sp.batch_axes
    sp_axes = ctx.sp.sp_axes
    b_, l_, d = x.shape
    replicated = ctx.decode
    # token-gather decode applies when expert hidden dims are FSDP-sharded
    # and there is a data axis to gather tokens over
    from .sharding import rules_for
    ff_axes = tuple(a for a in rules_for(cfg, "serve").get("expert_mlp", ())
                    if a in mesh.axis_names and mesh.shape[a] > 1)
    token_gather = (ctx.decode and ctx.ep_token_gather and bool(ff_axes)
                    and ba is not None)

    if replicated:
        xspec = P(ba, None, None)
    else:
        xspec = P(ba, sp_axes, None)

    if token_gather:
        e_local = p["wi_gate"].shape[0] // ep_degree
        in_specs = (xspec, P(None, None),
                    P(("model",), None, ff_axes),
                    P(("model",), None, ff_axes),
                    P(("model",), ff_axes, None))

        def body(x, rw, wg, wu, wo):
            t = x.reshape(-1, d)
            y, aux = _moe_token_gather_decode(
                t, rw, wg, wu, wo, cfg=cfg, ep_axes=ep_axes,
                e_local=e_local, ff_axes=ff_axes, batch_axes=ba)
            all_axes = tuple(mesh.axis_names)
            aux = lax.pmean(aux, all_axes)
            return y.reshape(x.shape), aux

        fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=(xspec, P()), check_vma=False)
        return fn(x, p["router"]["w"], p["wi_gate"], p["wi_up"], p["wo"])

    espec = lambda *rest: P(("model",), *rest)

    def body(x, rw, wg, wu, wo):
        t = x.reshape(-1, d)
        y, aux = _moe_local(
            t, rw, wg, wu, wo,
            cfg=cfg, ep_axes=ep_axes, ep_degree=ep_degree, replicated=replicated,
        )
        # aux is per-device; average over the whole mesh for a global scalar
        all_axes = tuple(mesh.axis_names)
        aux = lax.pmean(lax.pmean(aux, ep_axes), tuple(a for a in all_axes if a not in ep_axes))
        return y.reshape(x.shape), aux

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(xspec, P(None, None), espec(None, None), espec(None, None),
                  espec(None, None)),
        out_specs=(xspec, P()),
        check_vma=False,
    )
    y, aux = fn(x, p["router"]["w"], p["wi_gate"], p["wi_up"], p["wo"])
    return y, aux
