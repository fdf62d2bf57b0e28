"""Mixture-of-Experts FFN (DeepSeekMoE / DBRX style: shared + routed top-k).

Every token is routed by a softmax over the router's `num_experts` outputs
and takes its top-k experts.  Its gates are those top-k probabilities,
renormalised to sum to 1 only where `MoEConfig.norm_topk_prob` says so.
Shared experts see every token.  Weight-stationary experts are natural
AttentionLego tiles: each expert's FFN lives in its own PIM macros, with
its int8 weights quantized per expert per output channel (`_expert_mm`).

Two dispatches:

* Serving, `moe_ffn_serve`: dropless routing over the experts this layer
  holds.  A layer may hold one chip's share of an expert-parallel
  deployment, routed experts [first_held, first_held + held) of the
  router's `num_experts` (`MoEConfig`): it routes over all of them, adds
  only its held experts' part of the result and every shared expert whole.
  An assignment to an expert it does not hold adds nothing here; that part
  is the absent chips'.  Each held expert's buffer is the step's whole
  token stream (a zero gate where the token did not pick it), so no
  assignment is ever dropped, and a token's output does not depend on the
  other tokens of its step.  The layer also counts its load: assignments
  that reached held experts, and held experts that received any.
* Training, `moe_ffn_apply`: local capacity routing with token dropping.
  Each shard routes its own tokens (per-expert capacity C =
  ceil(T*k*cf/E)), sorts assignments by expert and builds an (E, C, D)
  dispatch buffer — no global sort, no (T, E, C) one-hot einsum.  Under a
  mesh the buffer goes through an all_to_all over the `model` axis (expert
  parallelism, `runtime/sharding.moe_shard_map`): each device computes its
  E/ep experts over every shard's slots.  A layer that holds a share trains
  through the dropless path.

The serving layer's ops carry the name scopes `moe_ffn/` (the whole FFN),
`moe_route/` inside it (router, top-k, dispatch and combine) and
`moe_experts/` (the held experts' matmuls and activation), by which a
profiler trace finds their device time.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import pim


def _expert_stack_init(key, n: int, d: int, f: int, glu: bool):
    keys = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "w_in": jax.random.normal(keys[0], (n, d, f), jnp.float32) * s_in,
        "w_out": jax.random.normal(keys[1], (n, f, d), jnp.float32) * s_out,
    }
    if glu:
        p["w_gate"] = jax.random.normal(keys[2], (n, d, f), jnp.float32) * s_in
    return p


def moe_ffn_init(key, cfg: ModelConfig):
    """Router over all `num_experts`; expert stacks of the held ones."""
    m = cfg.moe
    glu = cfg.activation in ("swiglu", "geglu")
    keys = jax.random.split(key, 3)
    p = {
        "router": jax.random.normal(keys[0], (cfg.d_model, m.num_experts),
                                    jnp.float32) * 0.02,
        "experts": _expert_stack_init(keys[1], m.held, cfg.d_model,
                                      cfg.d_ff, glu),
    }
    if m.num_shared:
        p["shared"] = _expert_stack_init(keys[2], m.num_shared, cfg.d_model,
                                         cfg.d_ff, glu)
    return p


def _act(x, kind):
    return jax.nn.gelu(x) if kind in ("gelu", "geglu") else jax.nn.silu(x)


def _expert_mm(xe: jax.Array, w: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Per-expert PIM matmul: (E, C, D) x (E, D, F) -> (E, C, F).

    Each expert is an independent weight-stationary PIM engine; quantization
    is per expert per output channel (vmapped behavioral model).
    """
    if not cfg.pim_linears:
        return jnp.einsum("ecd,edf->ecf", xe, w.astype(xe.dtype))
    return jax.vmap(
        lambda xc, wc: pim.pim_linear_apply({"w": wc}, xc, cfg.pim)
    )(xe, w)


def _ffn_stack(xe: jax.Array, params, cfg: ModelConfig) -> jax.Array:
    """(E, C, D) through the stacked expert FFNs."""
    if "w_gate" in params:
        g = _expert_mm(xe, params["w_gate"], cfg)
        h = _expert_mm(xe, params["w_in"], cfg)
        h = _act(g, cfg.activation) * h
    else:
        h = _act(_expert_mm(xe, params["w_in"], cfg), cfg.activation)
    return _expert_mm(h, params["w_out"], cfg)


def _route(params, xf: jax.Array, cfg: ModelConfig):
    """Softmax router over all `num_experts` outputs and its top-k:
    (probs (T, E), gate (T, k), idx (T, k))."""
    m = cfg.moe
    logits = jnp.dot(xf.astype(jnp.float32), params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return probs, gate, idx


def _aux_loss(probs: jax.Array, idx: jax.Array, cfg: ModelConfig):
    """Switch load-balance loss: E * sum_e f_e * P_e."""
    m = cfg.moe
    E = m.num_experts
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=1), axis=0)
    return E * jnp.sum(f_e * jnp.mean(probs, axis=0)) * m.router_aux_weight


def moe_ffn_held(params, xf: jax.Array, cfg: ModelConfig,
                 valid: Optional[jax.Array] = None):
    """Dropless routing of T tokens over the held experts.  xf: (T, D);
    `valid` (T,) marks real tokens (padding is computed but not counted).
    Returns (y (T, D), aux_loss, load): load is int32 (2,), the routed
    assignments of valid tokens that reached held experts and the held
    experts that received at least one."""
    m = cfg.moe
    T, D = xf.shape
    H = m.held
    with jax.named_scope("moe_ffn"):
        with jax.named_scope("moe_route"):
            probs, gate, idx = _route(params, xf, cfg)
            # (T, k, H): row j picks held expert idx - first_held; an
            # expert outside the share gives a zero row
            pick = jax.nn.one_hot(idx - m.first_held, H, dtype=jnp.float32)
            w = jnp.sum(pick * gate[:, :, None], axis=1)           # (T, H)
            xe = jnp.broadcast_to(xf, (H, T, D))
        with jax.named_scope("moe_experts"):
            ye = _ffn_stack(xe, params["experts"], cfg)            # (H, T, D)
        with jax.named_scope("moe_route"):
            y = jnp.sum(ye.astype(jnp.float32) * w.T[:, :, None],
                        axis=0).astype(xf.dtype)
        if m.num_shared:
            y = y + _ffn_stack(
                jnp.broadcast_to(xf, (m.num_shared,) + xf.shape),
                params["shared"], cfg,
            ).sum(0)
        hit = jnp.sum(pick, axis=1) > 0                            # (T, H)
        if valid is not None:
            hit = hit & valid[:, None]
        load = jnp.stack([jnp.sum(hit, dtype=jnp.int32),
                          jnp.sum(jnp.any(hit, axis=0), dtype=jnp.int32)])
    return y, _aux_loss(probs, idx, cfg), load


def moe_ffn_serve(params, x: jax.Array, cfg: ModelConfig,
                  valid: Optional[jax.Array] = None):
    """Serving: (B, S, D) -> (y (B, S, D), load (2,) int32), dropless over
    the held experts (`moe_ffn_held`); `valid` (B, S) marks real tokens."""
    B, S, D = x.shape
    y, _, load = moe_ffn_held(params, x.reshape(B * S, D), cfg,
                              None if valid is None else valid.reshape(-1))
    return y.reshape(B, S, D), load


def moe_ffn_local(
    params, xf: jax.Array, cfg: ModelConfig, ep_axis: Optional[str] = None
):
    """Route T local tokens with per-expert capacity (tokens past it are
    dropped). xf: (T, D). Returns (y: (T, D), aux_loss)."""
    m = cfg.moe
    if m.holds_share:
        raise ValueError("capacity routing needs every routed expert; a "
                         "layer that holds a share routes dropless")
    T, D = xf.shape
    E, k = m.num_experts, m.top_k
    probs, gate, idx = _route(params, xf, cfg)
    aux = _aux_loss(probs, idx, cfg)

    C = max(int(math.ceil(T * k * m.capacity_factor / E)), 1)
    ids = idx.reshape(-1)                                          # (T*k,)
    order = jnp.argsort(ids)                                       # local sort
    sorted_ids = ids[order]
    counts = jnp.bincount(ids, length=E)
    starts = jnp.cumsum(counts) - counts                           # (E,)
    rank = jnp.arange(T * k) - starts[sorted_ids]
    keep = rank < C
    slot = sorted_ids * C + rank                                   # (T*k,)
    token_of = order // k
    safe_slot = jnp.where(keep, slot, E * C)                       # overflow row
    xe = jnp.zeros((E * C + 1, D), xf.dtype).at[safe_slot].set(xf[token_of])
    xe = xe[: E * C].reshape(E, C, D)

    if ep_axis is not None:
        ep = jax.lax.axis_size(ep_axis)
        # (E, C, D) -> (E/ep, ep*C, D): every device gets its experts' slots
        xe = jax.lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        ye = _ffn_stack(xe, params["experts"], cfg)
        ye = jax.lax.all_to_all(ye, ep_axis, split_axis=1, concat_axis=0,
                                tiled=True)                        # (E, C, D)
    else:
        ye = _ffn_stack(xe, params["experts"], cfg)

    ye_flat = ye.reshape(E * C, D)
    gate_sorted = gate.reshape(-1)[order]
    w = jnp.where(keep, gate_sorted, 0.0).astype(xf.dtype)
    contrib = ye_flat[jnp.minimum(slot, E * C - 1)] * w[:, None]
    y = jnp.zeros((T, D), xf.dtype).at[token_of].add(contrib)

    if m.num_shared:
        y = y + _ffn_stack(
            jnp.broadcast_to(xf, (m.num_shared,) + xf.shape), params["shared"],
            cfg,
        ).sum(0)
    return y, aux


_MOE_TOKEN_CHUNK = 131_072   # global tokens per dispatch (bounds the
                             # (E, C, D) buffer: topk*cf*chunk*D elements)


def moe_ffn_apply(params, x: jax.Array, cfg: ModelConfig):
    """Training: (B, S, D) -> (y (B, S, D), aux_loss). Uses expert-parallel
    shard_map when a mesh with a `model` axis is ambient (set by the
    runtime); else the local path.  A layer that holds a share routes
    dropless (`moe_ffn_held`).

    Long prefill chunks the token stream so the capacity-dispatch buffer
    stays bounded regardless of sequence length."""
    B, S, D = x.shape
    if cfg.moe.holds_share:
        y, aux, _ = moe_ffn_held(params, x.reshape(B * S, D), cfg)
        return y.reshape(B, S, D), aux
    from repro.runtime import sharding as sh
    mesh = sh.current_mesh()

    def dispatch(xf):
        if mesh is not None and "model" in mesh.axis_names:
            return sh.moe_shard_map(params, xf, cfg, mesh)
        return moe_ffn_local(params, xf, cfg, None)

    T = B * S
    # chunk along the sequence axis (keeps every DP shard busy): smallest
    # divisor nc of S with B*S/nc <= chunk budget
    nc = 1
    if T > _MOE_TOKEN_CHUNK:
        for cand in range(2, S + 1):
            if S % cand == 0 and T // cand <= _MOE_TOKEN_CHUNK:
                nc = cand
                break
    if nc == 1:
        y, aux = dispatch(x.reshape(T, D))
        return y.reshape(B, S, D), aux
    xc = jnp.moveaxis(x.reshape(B, nc, S // nc, D), 1, 0)

    def body(acc, xb):
        y, aux = dispatch(xb.reshape(B * (S // nc), D))
        return acc + aux / nc, y.reshape(B, S // nc, D)

    aux, yc = jax.lax.scan(body, jnp.float32(0.0), xc)
    return jnp.moveaxis(yc, 0, 1).reshape(B, S, D), aux
