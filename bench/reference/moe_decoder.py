"""Plain float32 reference of a DeepSeekMoE decoder (RMSNorm, RoPE, MHA or
GQA attention; dense SwiGLU layers first, then softmax-routed SwiGLU
experts beside shared experts).

Straightforward `jax.numpy` at `Precision.HIGHEST`: exact softmax, float
K/V, float linears; no kernels, cache, quantization or batching.  It
imports nothing of the program and reads only the benchmark's own weights
through the accessor it is given.  One sequence at a time, one layer at a
time, so that it fits beside the weights.  Attention, RoPE and RMSNorm are
the dense decoder's (`dense_decoder.py`).

A MoE layer routes each token by a softmax over all `router_experts`
outputs and takes its top `num_experts_per_tok`; the gates are those
probabilities, renormalised to sum to 1 only where `norm_topk_prob` says.

Departures from the source (deepseek-ai/deepseek-moe-16b-base):
- the configuration may hold one chip's share of the routed experts
  (`n_routed_experts` of `router_experts`, from `first_held_expert` on):
  only the held experts' gated outputs are added, and a token's picks of
  other experts add nothing (the absent chips' part), as in the program;
- the shared experts are a stack of `n_shared_experts` SwiGLUs of
  `moe_intermediate_size`, summed; the source's one SwiGLU of
  `n_shared_experts * moe_intermediate_size` computes the same sum.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_decoder import BUCKET, _rms, _rope


def _attend(x, w, *, heads, kv_heads, head_dim, theta):
    S = x.shape[0]
    h = _rms(x, w["norm1"])
    q = (h @ w["wq"]).reshape(S, heads, head_dim)
    k = (h @ w["wk"]).reshape(S, kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(S, kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    g = heads // kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(head_dim))
    mask = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, heads * head_dim)
    return x + o @ w["wo"]


def _swiglu(h, w_gate, w_in, w_out):
    return (jax.nn.silu(h @ w_gate) * (h @ w_in)) @ w_out


def _dense_layer(x, w, **kw):
    x = _attend(x, w, **kw)
    h = _rms(x, w["norm2"])
    return x + _swiglu(h, w["w_gate"], w["w_in"], w["w_out"])


def _moe_layer(x, w, *, top_k, first_held, norm_topk_prob, **kw):
    x = _attend(x, w, **kw)
    h = _rms(x, w["norm2"])
    probs = jax.nn.softmax(h @ w["router"], axis=-1)          # (S, E)
    gate, idx = jax.lax.top_k(probs, top_k)                    # (S, k)
    if norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)
    ex = w["experts"]
    held = first_held + jnp.arange(ex["w_gate"].shape[0])      # (H,)
    # (S, H): each held expert's gate for each token, 0 where not picked
    g = jnp.sum(jnp.where(idx[:, :, None] == held, gate[:, :, None], 0.0),
                axis=1)
    y = jnp.zeros_like(h)
    for e in range(ex["w_gate"].shape[0]):
        y = y + g[:, e:e + 1] * _swiglu(h, ex["w_gate"][e], ex["w_in"][e],
                                        ex["w_out"][e])
    sh = w["shared"]
    for e in range(sh["w_gate"].shape[0]):
        y = y + _swiglu(h, sh["w_gate"][e], sh["w_in"][e], sh["w_out"][e])
    return x + y


def make(cfg: Dict):
    """`logits(weights, tokens, positions)` for configuration `cfg`.

    `weights` has `embed` (V, D), `head` (V, D), `final_norm` (D,) and
    `layer(i)`, which returns layer i's dict: `w_gate`, `w_in`, `w_out`
    for a dense layer, `router` (D, E) and the `experts` and `shared`
    stacks for a MoE layer, beside the attention's.  Returns float32
    logits (len(positions), V) as a NumPy array."""
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]))
    dense = jax.jit(lambda x, w: _dense_layer(x, w, **kw))
    moe = jax.jit(lambda x, w: _moe_layer(
        x, w, top_k=int(cfg["num_experts_per_tok"]),
        first_held=int(cfg["first_held_expert"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]), **kw))
    head = jax.jit(lambda x, pos, norm, table: _rms(x[pos], norm) @ table.T)
    n_dense = int(cfg["first_k_dense_replace"])

    def logits(weights, tokens: Sequence[int], positions: Sequence[int]):
        S = len(tokens)
        Sp = -(-S // BUCKET) * BUCKET
        ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, Sp - S)))
        with jax.default_matmul_precision("highest"):
            x = weights["embed"][ids].astype(jnp.float32)
            for i in range(cfg["num_hidden_layers"]):
                x = (dense if i < n_dense else moe)(x, weights["layer"](i))
            n = len(positions)
            pos = np.full(-(-n // BUCKET) * BUCKET, positions[-1], np.int32)
            pos[:n] = positions
            out = head(x, jnp.asarray(pos), weights["final_norm"],
                       weights["head"])
        return np.asarray(out)[:n]

    return logits
