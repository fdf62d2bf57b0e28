"""Plain float32 reference of a dense GQA decoder (RMSNorm, RoPE, SwiGLU).

Straightforward `jax.numpy` at `Precision.HIGHEST`: exact softmax, float
K/V, float linears; no kernels, cache, quantization or batching.  It
imports nothing of the program and reads only the benchmark's own weights
through the accessor it is given.  One sequence at a time, one layer at a
time, so that it fits beside the weights.

RoPE rotates the two halves of each head (the `rotate_half` convention)
with frequencies theta^(-2i/Dh); query head h reads KV head h // (H/Hkv).
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
BUCKET = 512        # sequences are padded to a multiple: fewer programs


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, theta):
    S, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, w, *, heads, kv_heads, head_dim, theta):
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
    x = x + o @ w["wo"]
    h = _rms(x, w["norm2"])
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"])) @ w["w_out"]


def make(cfg: Dict):
    """`logits(weights, tokens, positions)` for configuration `cfg`.

    `weights` has `embed` (V, D), `head` (V, D), `final_norm` (D,) and
    `layer(i)`, which returns layer i's dict.  Returns float32 logits
    (len(positions), V) as a NumPy array."""
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]))
    layer = jax.jit(lambda x, w: _layer(x, w, **kw))
    head = jax.jit(lambda x, pos, norm, table: _rms(x[pos], norm) @ table.T)

    def logits(weights, tokens: Sequence[int], positions: Sequence[int]):
        S = len(tokens)
        Sp = -(-S // BUCKET) * BUCKET
        ids = jnp.asarray(np.pad(np.asarray(tokens, np.int32), (0, Sp - S)))
        with jax.default_matmul_precision("highest"):
            x = weights["embed"][ids].astype(jnp.float32)
            for i in range(cfg["num_hidden_layers"]):
                x = layer(x, weights["layer"](i))
            n = len(positions)
            pos = np.full(-(-n // BUCKET) * BUCKET, positions[-1], np.int32)
            pos[:n] = positions
            out = head(x, jnp.asarray(pos), weights["final_norm"],
                       weights["head"])
        return np.asarray(out)[:n]

    return logits

