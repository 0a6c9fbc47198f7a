"""Mixture-of-Experts with expert parallelism.

Dispatch is index-based (sort-free rank computation + scatter/gather):
no (T, E, C) one-hot tensors are ever materialized — the peak extra
activation is the dispatched (E_local, C, d) buffer itself. Two EP modes:

- "psum" (baseline): activations are replicated across the "model" axis
  (they already are, since TP shards only the weights' inner axes); each
  model shard gathers the tokens routed to ITS experts, computes them, and
  contributes a partial output; one psum over "model" combines. Collective
  cost: one all-reduce of (T_local, d) regardless of top_k.
- "a2a" (optimized, §Perf): tokens all_to_all to expert-owner shards and
  back — moves only routed tokens (top_k/E_shards of the psum bytes).

Router aux-loss follows the standard load-balancing form
``E * sum_e f_e * P_e``; dropped-token counts are surfaced, never silent.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import dense_init
from ..distributed.sharding import active_rules, lshard


def moe_init(key, cfg: ModelConfig, stack: Tuple[int, ...] = ()) -> dict:
    ks = jax.random.split(key, 8)
    d, e, ff = cfg.d_model, cfg.moe_num_experts, cfg.moe_d_ff
    p = {
        "router": dense_init(ks[0], *stack, d, e, dtype=jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], *stack, e, d, ff, dtype=cfg.pdtype),
        "w_up": dense_init(ks[2], *stack, e, d, ff, dtype=cfg.pdtype),
        "w_down": dense_init(ks[3], *stack, e, ff, d, dtype=cfg.pdtype),
    }
    if cfg.moe_shared_experts:
        sff = cfg.moe_d_ff * cfg.moe_shared_experts
        p["shared_gate"] = dense_init(ks[4], *stack, d, sff, dtype=cfg.pdtype)
        p["shared_up"] = dense_init(ks[5], *stack, d, sff, dtype=cfg.pdtype)
        p["shared_down"] = dense_init(ks[6], *stack, sff, d, dtype=cfg.pdtype)
    return {"moe": p}


def _route(logits, cfg: ModelConfig):
    """top-k routing with normalized weights + aux load-balance loss."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    e = cfg.moe_num_experts
    f = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p_mean = probs.mean(axis=0)
    aux = e * jnp.sum(f * p_mean)
    return top_w, top_e, aux


def _expert_ranks(flat_e: jnp.ndarray, num_experts: int):
    """Rank of each assignment within its expert (scatter-free, via sort)."""
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.searchsorted(
        sorted_e, sorted_e, side="left").astype(jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)


def _expert_ffn(x_tok, expert_local, valid, w_gate, w_up, w_down,
                e_local: int, capacity: int):
    """Run local experts over routed tokens.

    x_tok (T, d) with per-token LOCAL expert id + validity; returns (T, d)
    outputs aligned with the inputs (invalid/over-capacity rows zero) and
    the dropped count.
    """
    t, d = x_tok.shape
    eid = jnp.where(valid, expert_local, e_local)
    rank = _expert_ranks(eid, e_local + 1)
    kept = valid & (rank < capacity)
    dropped = jnp.sum((valid & ~kept).astype(jnp.int32))
    slot = eid * capacity + rank
    x_e = jnp.zeros((e_local * capacity, d), x_tok.dtype)
    x_e = x_e.at[jnp.where(kept, slot, e_local * capacity)].set(
        x_tok, mode="drop").reshape(e_local, capacity, d)
    h = jnp.einsum("ecd,edf->ecf", x_e, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x_e, w_up)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, w_down)
    y_flat = y.reshape(e_local * capacity, d)
    out = jnp.where(kept[:, None],
                    y_flat[jnp.clip(slot, 0, e_local * capacity - 1)], 0)
    return out, dropped


def _moe_local(xf, router_w, w_gate, w_up, w_down, cfg: ModelConfig,
               e_offset, e_local: int, capacity: int):
    """Per-shard MoE: dispatch local tokens to local experts, partial out."""
    t, d = xf.shape
    logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
    top_w, top_e, aux = _route(logits, cfg)
    k = cfg.moe_top_k
    flat_e = top_e.reshape(t * k)
    flat_w = top_w.reshape(t * k).astype(xf.dtype)
    rank = _expert_ranks(flat_e, cfg.moe_num_experts)
    kept = rank < capacity
    dropped = jnp.sum((~kept).astype(jnp.int32))
    local = kept & (flat_e >= e_offset) & (flat_e < e_offset + e_local)
    slot = (flat_e - e_offset) * capacity + rank
    token_of = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    # dispatch: (E_loc*C, d)
    x_e = jnp.zeros((e_local * capacity, d), xf.dtype)
    x_e = x_e.at[jnp.where(local, slot, e_local * capacity)].set(
        xf[token_of], mode="drop")
    x_e = x_e.reshape(e_local, capacity, d)
    # expert FFNs (SwiGLU)
    h = jnp.einsum("ecd,edf->ecf", x_e, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x_e, w_up)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, w_down)
    # combine: per-assignment gather of this shard's partial expert outputs
    y_flat = y.reshape(e_local * capacity, d)
    contrib = y_flat[jnp.clip(slot, 0, e_local * capacity - 1)]
    contrib = jnp.where(local[:, None], contrib * flat_w[:, None], 0)
    out = jnp.zeros((t, d), xf.dtype).at[token_of].add(contrib)
    return out, aux, dropped


def moe_apply(p, x, cfg: ModelConfig):
    """MoE block: routed experts (+ optional shared experts)."""
    b, s, d = x.shape
    rules = active_rules()
    e = cfg.moe_num_experts
    router_w = p["router"]
    w_gate = p["w_gate"].astype(cfg.cdtype)
    w_up = p["w_up"].astype(cfg.cdtype)
    w_down = p["w_down"].astype(cfg.cdtype)

    ep_axis = rules.axis("experts") if rules is not None else None
    if ep_axis is None:
        xf = x.reshape(b * s, d)
        capacity = int(np.ceil(b * s * cfg.moe_top_k / e * cfg.capacity_factor))
        out, aux, dropped = _moe_local(xf, router_w, w_gate, w_up, w_down,
                                       cfg, 0, e, capacity)
        out = out.reshape(b, s, d)
    else:
        mesh = rules.mesh
        n_ep = mesh.shape[ep_axis]
        assert e % n_ep == 0, (e, n_ep)
        e_local = e // n_ep
        batch_axis = rules.axis("batch")
        dp = int(np.prod([mesh.shape[a] for a in (
            batch_axis if isinstance(batch_axis, tuple) else (batch_axis,))]))
        if b % dp:  # e.g. batch=1 long-context decode: replicate tokens
            batch_axis = None
            dp = 1
        t_local = b * s // dp
        capacity = int(np.ceil(t_local * cfg.moe_top_k / e * cfg.capacity_factor))
        # optional expert-internal FF sharding (weight-stationary serving):
        # logical axis "moe_ff" — inner ff dim sharded, down-proj partials
        # psum'd together with the EP combine.
        ff_axis = rules.axis("moe_ff")
        if ff_axis is not None:
            ff_axes = ff_axis if isinstance(ff_axis, tuple) else (ff_axis,)
            n_ff = int(np.prod([mesh.shape[a] for a in ff_axes]))
            if cfg.moe_d_ff % n_ff:
                ff_axis = None
        psum_axes = (ep_axis,) if ff_axis is None else \
            (ep_axis,) + (ff_axis if isinstance(ff_axis, tuple) else (ff_axis,))

        def body(x_l, router_l, wg_l, wu_l, wd_l):
            bl, sl, _ = x_l.shape
            e0 = jax.lax.axis_index(ep_axis) * e_local
            out, aux, dropped = _moe_local(
                x_l.reshape(bl * sl, d), router_l, wg_l, wu_l, wd_l, cfg,
                e0, e_local, capacity)
            # combine in the compute dtype: halves the EP wire bytes vs an
            # f32 psum (top-8 partials in bf16 are well within tolerance)
            out = jax.lax.psum(out.astype(cfg.cdtype), psum_axes)
            aux = jax.lax.pmean(aux, ep_axis)
            dropped = jax.lax.psum(dropped, ep_axis)
            return out.reshape(bl, sl, d), aux, dropped

        use_a2a = (cfg.moe_impl == "a2a" and ff_axis is None
                   and (b * s // dp) % n_ep == 0)

        def body_a2a(x_l, router_l, wg_l, wu_l, wd_l):
            """all_to_all EP: each shard routes ITS token slice to expert
            owners, computes, routes back, and all-gathers the combined
            slices — wire bytes ∝ top_k/n_ep instead of a dense psum."""
            bl, sl, _ = x_l.shape
            t_all = bl * sl
            t_chunk = t_all // n_ep
            me = jax.lax.axis_index(ep_axis)
            xf = jax.lax.dynamic_slice_in_dim(
                x_l.reshape(t_all, d), me * t_chunk, t_chunk, axis=0)
            logits = xf.astype(jnp.float32) @ router_l.astype(jnp.float32)
            top_w, top_e, aux = _route(logits, cfg)
            k = cfg.moe_top_k
            flat_e = top_e.reshape(t_chunk * k)
            flat_w = top_w.reshape(t_chunk * k).astype(xf.dtype)
            dest = flat_e // e_local
            # per-destination slotting
            rank = _expert_ranks(dest, n_ep)
            cap = int(np.ceil(t_chunk * k / n_ep * 2.0))
            kept = rank < cap
            n_drop_route = jnp.sum((~kept).astype(jnp.int32))
            slot = jnp.where(kept, dest * cap + rank, n_ep * cap)
            token_of = jnp.repeat(jnp.arange(t_chunk, dtype=jnp.int32), k)
            send_x = jnp.zeros((n_ep * cap, d), xf.dtype).at[slot].set(
                xf[token_of], mode="drop")
            send_e = jnp.full((n_ep * cap,), e, jnp.int32).at[slot].set(
                flat_e, mode="drop")
            recv_x = jax.lax.all_to_all(send_x.reshape(n_ep, cap, d),
                                        ep_axis, 0, 0, tiled=True)
            recv_e = jax.lax.all_to_all(send_e.reshape(n_ep, cap),
                                        ep_axis, 0, 0, tiled=True)
            recv_x = recv_x.reshape(n_ep * cap, d)
            recv_e = recv_e.reshape(n_ep * cap)
            e0 = me * e_local
            valid = (recv_e >= e0) & (recv_e < e0 + e_local)
            cap_e = int(np.ceil(n_ep * cap / e_local * 1.0)) + 8
            y, n_drop_cap = _expert_ffn(recv_x, recv_e - e0, valid,
                                        wg_l, wu_l, wd_l, e_local, cap_e)
            back = jax.lax.all_to_all(y.reshape(n_ep, cap, d),
                                      ep_axis, 0, 0, tiled=True)
            back = back.reshape(n_ep * cap, d)
            contrib = back[jnp.clip(slot, 0, n_ep * cap - 1)]
            contrib = jnp.where(kept[:, None], contrib * flat_w[:, None], 0)
            out_chunk = jnp.zeros((t_chunk, d), xf.dtype).at[token_of].add(contrib)
            out = jax.lax.all_gather(out_chunk, ep_axis, tiled=True)
            aux = jax.lax.pmean(aux, ep_axis)
            dropped = jax.lax.psum(n_drop_route + n_drop_cap, ep_axis)
            return out.reshape(bl, sl, d), aux, dropped

        out, aux, dropped = jax.shard_map(
            body_a2a if use_a2a else body, mesh=mesh,
            in_specs=(P(batch_axis, None, None), P(),
                      P(ep_axis, None, ff_axis),
                      P(ep_axis, None, ff_axis),
                      P(ep_axis, ff_axis, None)),
            out_specs=(P(batch_axis, None, None), P(), P()),
            check_vma=False,
        )(x, router_w, w_gate, w_up, w_down)

    if cfg.moe_shared_experts:
        g = x @ p["shared_gate"].astype(cfg.cdtype)
        u = x @ p["shared_up"].astype(cfg.cdtype)
        shared = lshard(jax.nn.silu(g) * u, "batch", "seq", "ffn")
        out = out + shared @ p["shared_down"].astype(cfg.cdtype)
    return lshard(out, "batch", "seq", None), aux, dropped
