"""Public model bundle: build_model(cfg) -> Model with init/train/serve fns."""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[..., Any]                 # (key) -> params
    forward_train: Callable[..., Any]        # (params, batch) -> (logits, aux)
    loss: Callable[..., Any]                 # (params, batch) -> (loss, metrics)
    init_cache: Callable[..., Any]           # (batch, max_len) -> cache
    forward_serve: Callable[..., Any]        # (params, batch, cache, offset[, enc_out])


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


_CE_CHUNK = 512


def chunked_ce_from_hidden(hidden: jax.Array, head_table: jax.Array,
                           labels: jax.Array) -> jax.Array:
    """Sequence-chunked CE: full (B, S, V) logits are never materialized —
    each chunk computes its own logits tile against the (vocab-sharded)
    head table.  hidden: (B, S, D) (positions 0..S-1 predict 1..S)."""
    B, S, D = hidden.shape
    h = hidden[:, :-1]
    y = labels[:, 1:]
    T = h.shape[1]
    cq = _CE_CHUNK
    if T <= cq or T % cq:
        logits = jnp.einsum("bsd,vd->bsv", h, head_table.astype(h.dtype))
        return cross_entropy(logits, y)
    nc = T // cq
    hc = jnp.moveaxis(h.reshape(B, nc, cq, D), 1, 0)
    yc = jnp.moveaxis(y.reshape(B, nc, cq), 1, 0)

    def body(acc, args):
        hb, yb = args
        logits = jnp.einsum("bsd,vd->bsv", hb, head_table.astype(hb.dtype))
        lf = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, yb[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, yc))
    return total / (B * T)


def deploy_tree(params, cfg: ModelConfig):
    """Convert every PIM linear's fp master weight to deployed int8 macro
    contents (the paper's one-time weight load).  Non-PIM leaves (norms,
    embeddings, gates, expert stacks) are unchanged."""
    from repro.core import pim as _pim

    def deploy_one(node):
        if node["w"].ndim == 2:
            return _pim.deploy_params(node, cfg.pim)
        # stacked (R, d_in, d_out) layer stacks: per-layer quantization
        w_q, w_scale = jax.vmap(
            lambda w: _pim.quantize_weights(w, cfg.pim))(node["w"])
        out = {"w_q": w_q, "w_scale": w_scale}
        if "b" in node:
            out["b"] = node["b"]
        return out

    def visit(node):
        if isinstance(node, dict):
            if ("w" in node and hasattr(node["w"], "ndim")
                    and node["w"].ndim in (2, 3)
                    and set(node) <= {"w", "b"}):
                return deploy_one(node)
            return {k: visit(v) for k, v in node.items()}
        if isinstance(node, (tuple, list)):
            return type(node)(visit(v) for v in node)
        return node

    return visit(params)


def param_count_exact(cfg: ModelConfig) -> int:
    """Exact parameter count via eval_shape (no allocation)."""
    shapes = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return sum(int(x.size) for x in jax.tree.leaves(shapes))


def build_model(cfg: ModelConfig) -> Model:
    def init(key):
        return T.init_params(key, cfg)

    def forward_train(params, batch):
        return T.forward_train(params, batch, cfg)

    def loss(params, batch):
        hidden, aux = T.forward_hidden(params, batch, cfg)
        head = params["embed"] if cfg.tie_embeddings else params["unembed"]
        ce = chunked_ce_from_hidden(hidden, head["table"], batch["tokens"])
        total = ce + aux
        return total, {"loss": total, "ce": ce, "aux": aux}

    def init_cache(batch, max_len, ragged=False, page_size=0, num_pages=0):
        return T.init_cache(cfg, batch, max_len, ragged=ragged,
                            page_size=page_size, num_pages=num_pages)

    def forward_serve(params, batch, cache, offset, enc_out=None,
                      seq_lens=None, pages=None, decode_rows=None,
                      logit_positions=None, verify_len=1, moe_load=False):
        return T.forward_serve(params, batch, cache, offset, cfg,
                               enc_out=enc_out, seq_lens=seq_lens,
                               pages=pages, decode_rows=decode_rows,
                               logit_positions=logit_positions,
                               verify_len=verify_len, moe_load=moe_load)

    return Model(cfg, init, forward_train, loss, init_cache, forward_serve)
