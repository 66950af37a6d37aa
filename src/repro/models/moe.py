"""Mixture-of-Experts FFN.

Three execution paths, same math:

* ``moe_ffn_ref``      — O(E) python loop, no capacity drops.  Oracle for
                         tests (small E only).
* ``moe_ffn_dispatch`` — scatter-based capacity dispatch, single logical
                         device (jit/GSPMD).  Used for decode steps and
                         CPU smoke tests.
* ``moe_ffn_ep``       — expert-parallel production path: runs inside
                         ``shard_map``; local top-k routing -> local capacity
                         dispatch -> ``all_to_all`` over the EP axis ->
                         local expert GEMMs -> reverse ``all_to_all`` ->
                         weighted combine.  This is the all-to-all traffic
                         the paper's full-mesh HyperX dimensions serve well
                         (DESIGN.md §3); EP shard bytes feed the collective
                         roofline term.

Routing: softmax over experts, top-k, weights renormalized over the chosen k
(Mixtral-style).  A Switch-style load-balance auxiliary loss is returned by
each path.  Tokens beyond an expert's capacity are dropped (standard GShard
behaviour); the reference path never drops, and tests use capacity_factor
large enough that dispatch == ref.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from .layers import dense_init, swiglu, swiglu_init


def moe_init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), jnp.float32),
        "experts": {
            "w_gate": jax.vmap(
                lambda k: dense_init(k, (d, m.d_expert), dtype))(
                    jax.random.split(ks[1], m.n_experts)),
            "w_up": jax.vmap(
                lambda k: dense_init(k, (d, m.d_expert), dtype))(
                    jax.random.split(ks[2], m.n_experts)),
            "w_down": jax.vmap(
                lambda k: dense_init(k, (m.d_expert, d), dtype,
                                     in_axis_size=m.d_expert))(
                    jax.random.split(ks[3], m.n_experts)),
        },
    }
    if m.n_shared_experts:
        p["shared"] = swiglu_init(ks[4], d, m.d_expert * m.n_shared_experts,
                                  dtype)
    return p


def _route(router_w, x_flat, cfg: ModelConfig):
    """x_flat (T, d) -> (top_w (T,k) f32, top_i (T,k) i32, aux_loss f32)."""
    m = cfg.moe
    logits = (x_flat.astype(jnp.float32) @ router_w)            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = lax.top_k(probs, m.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    E = m.n_experts
    f = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = f / jnp.maximum(top_i.size, 1)
    pbar = probs.mean(0)
    aux = E * jnp.sum(f * pbar)
    return top_w, top_i, aux


def _expert_ffn(experts, h):
    """h (E, C, d) -> (E, C, d) via per-expert SwiGLU (batched GEMMs)."""
    g = jnp.einsum("ecd,edf->ecf", h, experts["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", h, experts["w_up"])
    a = (jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype)) * u
    return jnp.einsum("ecf,efd->ecd", a, experts["w_down"])


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    return max(1, math.ceil(n_tokens * m.top_k / m.n_experts
                            * m.capacity_factor))


# --------------------------------------------------------------------------
# reference (no drops, python loop over experts)
# --------------------------------------------------------------------------


def moe_ffn_ref(p, x, cfg: ModelConfig):
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    top_w, top_i, aux = _route(p["router"], xf, cfg)
    y = jnp.zeros_like(xf, dtype=jnp.float32)
    for e in range(cfg.moe.n_experts):
        w_e = jnp.sum(top_w * (top_i == e), axis=-1)            # (T,)
        ex = {k: v[e] for k, v in p["experts"].items()}
        h = swiglu({"w_gate": ex["w_gate"], "w_up": ex["w_up"],
                    "w_down": ex["w_down"]}, xf)
        y = y + w_e[:, None] * h.astype(jnp.float32)
    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# scatter dispatch (single logical device / GSPMD)
# --------------------------------------------------------------------------


def _dispatch(xf, top_w, top_i, E: int, C: int):
    """Pack routed tokens into (E, C, d) buffers.

    Returns (buf, eid, pos, keep, wflat):
      eid/pos/keep/wflat are (T*k,) routing records for the combine step.
    """
    T, d = xf.shape
    k = top_i.shape[1]
    eid = top_i.reshape(-1)                                     # (T*k,)
    wflat = top_w.reshape(-1)
    # position of each (token, slot) within its expert's capacity buffer:
    # rank among earlier records routed to the same expert.
    onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)            # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1)                      # (T*k, E)
    pos = jnp.take_along_axis(pos, eid[:, None], axis=1)[:, 0]  # (T*k,)
    keep = pos < C
    x_rep = jnp.repeat(xf, k, axis=0)                           # (T*k, d)
    safe_e = jnp.where(keep, eid, 0)
    safe_p = jnp.where(keep, pos, 0)
    buf = jnp.zeros((E, C, d), xf.dtype)
    buf = buf.at[safe_e, safe_p].add(
        jnp.where(keep[:, None], x_rep, 0).astype(xf.dtype))
    return buf, eid, pos, keep, wflat


def _combine(h, eid, pos, keep, wflat, T: int, k: int):
    """Gather expert outputs back to tokens and weight-sum over k slots."""
    safe_e = jnp.where(keep, eid, 0)
    safe_p = jnp.where(keep, pos, 0)
    y_rep = h[safe_e, safe_p]                                   # (T*k, d)
    y_rep = jnp.where(keep[:, None], y_rep, 0)
    y_rep = y_rep * wflat[:, None].astype(y_rep.dtype)
    return y_rep.reshape(T, k, -1).sum(axis=1)


def moe_ffn_dispatch(p, x, cfg: ModelConfig,
                     capacity: Optional[int] = None):
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    m = cfg.moe
    C = capacity or _capacity(T, cfg)
    top_w, top_i, aux = _route(p["router"], xf, cfg)
    buf, eid, pos, keep, wflat = _dispatch(xf, top_w, top_i, m.n_experts, C)
    h = _expert_ffn(p["experts"], buf)
    y = _combine(h, eid, pos, keep, wflat, T, m.top_k).astype(x.dtype)
    if "shared" in p:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# tensor-parallel-f MoE (inside shard_map) — for E < TP degree (Mixtral)
# --------------------------------------------------------------------------


def moe_ffn_tp_f(p, x, cfg: ModelConfig, tp_axis: str,
                 fsdp_axis=None, capacity: Optional[int] = None):
    """Megatron-style MoE for few-expert models: call INSIDE shard_map.

    x: (B_loc, S, d) — batch sharded on the dp axes, REPLICATED across
    ``tp_axis`` (the non-sequence-parallel activation layout).  Experts
    keep all E locally but shard the FFN-hidden dim over ``tp_axis``
    (stored spec P(None, fsdp, tp) / P(None, tp, fsdp)); dispatch is fully
    local, the down-projection's f-partials are psum'd over tp — exact
    because every tp shard holds identical tokens.  Replaces the
    partitioner's (E, C_global, d) dispatch-buffer all-reduces (~8.8 TiB
    per step on mixtral train_4k) with one (E, C_loc, d) psum per call.
    """
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    m = cfg.moe
    C = capacity or _capacity(T, cfg)
    top_w, top_i, aux = _route(p["router"], xf, cfg)
    buf, eid, pos, keep, wflat = _dispatch(xf, top_w, top_i, m.n_experts, C)
    experts = p["experts"]
    if fsdp_axis is not None:
        # ZeRO gather of the d-model dim only (the f dim stays tp-sharded)
        experts = {
            "w_gate": lax.all_gather(experts["w_gate"], fsdp_axis, axis=1,
                                     tiled=True),
            "w_up": lax.all_gather(experts["w_up"], fsdp_axis, axis=1,
                                   tiled=True),
            "w_down": lax.all_gather(experts["w_down"], fsdp_axis, axis=2,
                                     tiled=True),
        }
    h = _expert_ffn(experts, buf)          # (E, C, d), partial over f-shards
    h = lax.psum(h, tp_axis)
    y = _combine(h, eid, pos, keep, wflat, T, m.top_k).astype(x.dtype)
    aux = lax.pmean(aux, tp_axis)
    if "shared" in p:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# expert-parallel (inside shard_map)
# --------------------------------------------------------------------------


def moe_ffn_ep(p, x, cfg: ModelConfig, ep_axis: str,
               capacity: Optional[int] = None,
               partial_ffn_axis: Optional[str] = None):
    """Expert-parallel MoE FFN — call INSIDE shard_map.

    x: local tokens (B_loc, S_loc, d).
    p["experts"]: local expert shard, leaves (E_loc, ...).
    p["router"]/p["shared"]: replicated.

    The EP axis carries two all-to-alls of (E, C_loc, d) bytes per call —
    this is the collective the MPHX mapping optimizes (core/mapping.py).

    ``partial_ffn_axis``: weight-stationary mode — expert weights arrive
    sharded on the FFN-hidden dim over this axis and are NEVER gathered.
    Since tokens differ across that axis, the dispatch buffer is
    all-gathered over it first (every shard sees every shard's tokens for
    its local experts), each shard computes its f-slice of the FFN, and the
    partial outputs are reduce-scattered back to the owning shard.  Trades
    per-use expert-weight all-gathers (ZeRO-3) for activation
    gather+scatter — a win whenever token bytes < expert-weight bytes
    (EXPERIMENTS.md §Perf).
    """
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    T = xf.shape[0]
    m = cfg.moe
    ep = lax.axis_size(ep_axis)
    E_loc = m.n_experts // ep
    if E_loc * ep != m.n_experts:
        raise ValueError(f"{m.n_experts} experts not divisible by EP={ep}")
    C = capacity or _capacity(T, cfg)

    top_w, top_i, aux = _route(p["router"], xf, cfg)
    buf, eid, pos, keep, wflat = _dispatch(xf, top_w, top_i, m.n_experts, C)
    # (E, C, d) -> (ep, E_loc, C, d) -> exchange: each peer receives the
    # slice of my buffer destined for its experts; afterwards axis 0 indexes
    # the SOURCE shard.
    buf = buf.reshape(ep, E_loc, C, d)
    buf = lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    # local experts see tokens from every source shard
    buf = buf.transpose(1, 0, 2, 3).reshape(E_loc, ep * C, d)
    if partial_ffn_axis is not None:
        # weight-stationary: gather every fsdp-shard's tokens, compute the
        # local f-slice for all of them, reduce-scatter outputs back so
        # each shard keeps full-FFN results for its OWN tokens.
        buf = lax.all_gather(buf, partial_ffn_axis, axis=1, tiled=True)
        h = _expert_ffn(p["experts"], buf)      # partial over the f dim
        h = lax.psum_scatter(h, partial_ffn_axis, scatter_dimension=1,
                             tiled=True)        # (E_loc, ep*C, d), exact
    else:
        h = _expert_ffn(p["experts"], buf)                      # (E_loc, ep*C, d)
    # reverse exchange: axis 0 = destination (source) shard
    h = h.reshape(E_loc, ep, C, d).transpose(1, 0, 2, 3)
    h = lax.all_to_all(h, ep_axis, split_axis=0, concat_axis=0, tiled=False)
    # axis 0 = expert-owner shard -> global expert index order
    h = h.reshape(m.n_experts, C, d)
    y = _combine(h, eid, pos, keep, wflat, T, m.top_k).astype(x.dtype)
    # aux loss: average over EP shards (tokens differ per shard)
    aux = lax.pmean(aux, ep_axis)
    if "shared" in p:
        y = y + swiglu(p["shared"], xf)
    return y.reshape(B, S, d), aux
