"""Model assembly: pattern-scanned block stacks for every assigned arch.

Layers are grouped by the arch's `block_pattern` (e.g. recurrentgemma's
(rglru, rglru, attn)); parameters for each pattern position are stacked over
repetitions and the forward is a jax.lax.scan over repetitions with the
pattern unrolled inside — this keeps the HLO size O(pattern) instead of
O(num_layers), which is what makes the 88-layer 123B dry-run compile.
A remainder tail (num_layers % pattern) is unrolled separately.

Serve state (KV caches / recurrent states) is stacked the same way and
scanned alongside the parameters.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, _pattern_kinds
from repro.core import pim
from repro.models import blocks as B
from repro.models import layers as L


# ---------------------------------------------------------------------------
# block-kind registry
# ---------------------------------------------------------------------------
def _block_init(kind: str, key, cfg: ModelConfig):
    if kind in ("attn", "attn_local", "enc_attn"):
        return B.attn_block_init(key, cfg)
    if kind == "moe":
        return B.attn_block_init(key, cfg, moe=True)
    if kind == "xattn":
        return B.attn_block_init(key, cfg, cross=True)
    if kind == "mlstm":
        return B.mlstm_block_init(key, cfg)
    if kind == "slstm":
        return B.slstm_block_init(key, cfg)
    if kind == "rglru":
        return B.rglru_block_init(key, cfg)
    raise ValueError(kind)


def _block_fwd_train(kind: str, params, x, pos_ids, cfg: ModelConfig,
                     enc_out=None):
    if kind in ("attn", "moe"):
        return B.attn_block_fwd_train(params, x, pos_ids, cfg,
                                      window=0, causal=cfg.causal)
    if kind == "attn_local":
        return B.attn_block_fwd_train(params, x, pos_ids, cfg,
                                      window=cfg.window, causal=True)
    if kind == "enc_attn":
        return B.attn_block_fwd_train(params, x, pos_ids, cfg,
                                      window=0, causal=False)
    if kind == "xattn":
        return B.xattn_block_fwd_train(params, x, enc_out, pos_ids, cfg)
    if kind == "mlstm":
        return B.mlstm_block_fwd_train(params, x, pos_ids, cfg)
    if kind == "slstm":
        return B.slstm_block_fwd_train(params, x, pos_ids, cfg)
    if kind == "rglru":
        return B.rglru_block_fwd_train(params, x, pos_ids, cfg)
    raise ValueError(kind)


def _block_init_state(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                      ragged: bool = False, page_size: int = 0,
                      num_pages: int = 0):
    if page_size and kind not in ("attn", "moe"):
        raise NotImplementedError(
            f"paged KV is only supported for attention stacks (got {kind!r})")
    if kind in ("attn", "moe"):
        return B.attn_block_init_state(cfg, batch, max_len, ragged=ragged,
                                       page_size=page_size,
                                       num_pages=num_pages)
    if kind == "attn_local":
        return B.attn_block_init_state(cfg, batch, max_len, window=cfg.window)
    if kind == "xattn":
        return B.xattn_block_init_state(cfg, batch, max_len)
    if kind == "mlstm":
        return B.mlstm_block_init_state(cfg, batch, max_len)
    if kind == "slstm":
        return B.slstm_block_init_state(cfg, batch, max_len)
    if kind == "rglru":
        return B.rglru_block_init_state(cfg, batch, max_len)
    raise ValueError(kind)


def _block_fwd_serve(kind: str, params, x, state, offset, cfg: ModelConfig,
                     enc_out=None, seq_lens=None, pages=None,
                     decode_rows=None, verify_len: int = 1):
    if kind in ("attn", "moe"):
        return B.attn_block_fwd_serve(params, x, state, offset, cfg,
                                      window=0, causal=cfg.causal,
                                      seq_lens=seq_lens, pages=pages,
                                      decode_rows=decode_rows,
                                      verify_len=verify_len)
    if kind == "attn_local":
        return B.attn_block_fwd_serve(params, x, state, offset, cfg,
                                      window=cfg.window, causal=True,
                                      seq_lens=seq_lens, pages=pages,
                                      decode_rows=decode_rows,
                                      verify_len=verify_len)
    if kind == "xattn":
        return B.xattn_block_fwd_serve(params, x, state, offset, cfg,
                                       enc_out=enc_out)
    if kind == "mlstm":
        return B.mlstm_block_fwd_serve(params, x, state, offset, cfg)
    if kind == "slstm":
        return B.slstm_block_fwd_serve(params, x, state, offset, cfg)
    if kind == "rglru":
        return B.rglru_block_fwd_serve(params, x, state, offset, cfg)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# pattern layout helpers
# ---------------------------------------------------------------------------
def pattern_layout(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    """(pattern, repetitions, tail_kinds).

    num_layers = num_dense_layers (unrolled MoE dense prefix, if any)
               + R * len(pattern) (scanned)  + len(tail) (unrolled remainder).
    """
    pat = cfg.block_pattern
    n = cfg.num_layers
    if cfg.num_dense_layers and "moe" in pat:
        n -= cfg.num_dense_layers
    R = n // len(pat)
    rem = n - R * len(pat)
    tail = (pat * (rem // len(pat) + 1))[:rem]
    return pat, R, tail


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    pat, R, tail = pattern_layout(cfg)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {"embed": L.embed_init(keys[0], cfg.vocab_size,
                                                    cfg.d_model)}
    if not cfg.tie_embeddings:
        params["unembed"] = {
            "table": jax.random.normal(keys[1], (cfg.vocab_size, cfg.d_model),
                                       jnp.float32) * 0.02
        }
    if cfg.pos == "absolute":
        params["pos_embed"] = jax.random.normal(
            keys[2], (cfg.max_seq_len, cfg.d_model), jnp.float32) * 0.02
    if cfg.num_image_patches:
        params["img_proj"] = pim.pim_linear_init(keys[3], cfg.d_model,
                                                 cfg.d_model)
    # stacked blocks per pattern position
    stacks = []
    for j, kind in enumerate(pat):
        kj = jax.random.fold_in(keys[4], j)
        stack = jax.vmap(lambda k: _block_init(kind, k, cfg))(
            jax.random.split(kj, R))
        stacks.append(stack)
    params["blocks"] = tuple(stacks)
    params["tail"] = tuple(
        _block_init(kind, jax.random.fold_in(keys[5], i), cfg)
        for i, kind in enumerate(tail)
    )
    # dense prefix of MoE archs (deepseek): its own dense blocks, at the
    # dense FFN width, ahead of the stacks and the tail
    if cfg.num_dense_layers and "moe" in pat:
        params["dense_prefix"] = tuple(
            B.attn_block_init(jax.random.fold_in(keys[6], i), cfg,
                              d_ff=cfg.resolved_dense_d_ff)
            for i in range(cfg.num_dense_layers)
        )
    params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    if cfg.is_encoder_decoder:
        ek = jax.random.split(keys[7], 3)
        params["enc_blocks"] = jax.vmap(
            lambda k: _block_init("enc_attn", k, cfg)
        )(jax.random.split(ek[0], cfg.num_encoder_layers))
        params["enc_norm"] = L.norm_init(cfg.d_model, cfg.norm)
    return params


# ---------------------------------------------------------------------------
# encoder (whisper backbone; frontend is a stub feeding frame embeddings)
# ---------------------------------------------------------------------------
def encode(params, frames: jax.Array, cfg: ModelConfig) -> jax.Array:
    """frames: (B, Se, D) precomputed frame embeddings (conv-stem stub)."""
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
    pos_ids = jnp.arange(x.shape[1])

    def body(x, p):
        y, _ = B.attn_block_fwd_train(p, x, pos_ids, cfg, window=0,
                                      causal=False)
        return y, None

    x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return L.norm_apply(params["enc_norm"], x, cfg.norm)


# ---------------------------------------------------------------------------
# train forward
# ---------------------------------------------------------------------------
def _embed_inputs(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
                  offset=0):
    tokens = batch["tokens"]
    x = L.embed_apply(params["embed"], tokens, jnp.dtype(cfg.compute_dtype))
    if cfg.pos == "absolute":
        S = tokens.shape[1]
        if getattr(offset, "ndim", 0) >= 1:
            # ragged slots: per-row position gather
            pos_ids = jnp.clip(offset[:, None] + jnp.arange(S)[None, :],
                               0, params["pos_embed"].shape[0] - 1)
            pe = params["pos_embed"][pos_ids]              # (B, S, D)
        else:
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_embed"], offset, S, axis=0)
        x = x + pe.astype(x.dtype)
    if cfg.num_image_patches and "image_embeds" in batch:
        # stub VLM fusion: project patch embeddings into the first P positions
        img = pim.pim_linear_apply(
            params["img_proj"],
            batch["image_embeds"].astype(x.dtype), cfg.pim, cfg.pim_linears)
        P = min(cfg.num_image_patches, x.shape[1])
        x = x.at[:, :P].add(img[:, :P])
    return x


def forward_train(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Returns (logits (B,S,V), aux_loss scalar)."""
    x, aux = forward_hidden(params, batch, cfg)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return L.unembed_apply(head, x), aux


def forward_hidden(params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Final normed hidden states (B,S,D) + aux loss (no unembedding —
    the loss computes vocab-sharded chunked CE without full logits)."""
    pat, R, tail = pattern_layout(cfg)
    x = _embed_inputs(params, batch, cfg)
    S = x.shape[1]
    pos_ids = jnp.arange(S)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc_out = encode(params, batch["frames"], cfg)

    from repro.runtime.sharding import constrain, dp_axes_spec
    ba = dp_axes_spec()

    def one_block(kind):
        def f(x, p):
            x, a = _block_fwd_train(kind, p, x, pos_ids, cfg, enc_out=enc_out)
            # boundary activations sequence-sharded over the model axis
            # (Megatron-style SP: bounds the per-device residual-stream
            # memory saved for backward)
            return constrain(x, ba, "model", None), a
        # PER-BLOCK remat: a heterogeneous pattern (e.g. xlstm's 7 mlstm +
        # 1 slstm) must not hold every block's recomputed intermediates
        # live at once during the group backward (56 GB -> ~13 GB on the
        # xlstm train cell; EXPERIMENTS.md §Perf extras)
        return jax.checkpoint(f) if cfg.remat != "none" else f

    block_fns = [one_block(kind) for kind in pat]

    def layer_group(x, group_params):
        aux = jnp.float32(0.0)
        for j in range(len(pat)):
            x, a = block_fns[j](x, group_params[j])
            aux += a
        return x, aux

    if "dense_prefix" in params:
        for p in params["dense_prefix"]:
            x, _ = _block_fwd_train("attn", p, x, pos_ids, cfg)

    def scan_body(carry, group_params):
        x, aux = carry
        x, a = layer_group(x, group_params)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.float32(0.0)),
                               params["blocks"])
    for i, kind in enumerate(tail):
        x, a = _block_fwd_train(kind, params["tail"][i], x, pos_ids, cfg,
                                enc_out=enc_out)
        aux += a
    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    return x, aux


# ---------------------------------------------------------------------------
# serve: cache init, prefill, decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               ragged: bool = False, page_size: int = 0, num_pages: int = 0):
    """Serve-state tree.  With `ragged=True` every KV cache carries a (B,)
    per-slot `length` vector (all zeros = every slot empty/inactive) — the
    layout the continuous-batching scheduler requires.

    With `page_size > 0` every attention layer's state is instead a
    `PagedKVCache` pool of `num_pages` pages (page 0 reserved as the trash
    page; no batch axis — slots are rows of the page table the caller
    threads through `forward_serve(pages=...)`)."""
    pat, R, tail = pattern_layout(cfg)

    def one(kind):
        return _block_init_state(kind, cfg, batch, max_len, ragged=ragged,
                                 page_size=page_size, num_pages=num_pages)

    def stacked(kind):
        st = one(kind)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (R,) + a.shape), st)

    cache = {
        "blocks": tuple(stacked(kind) for kind in pat),
        "tail": tuple(one(kind) for kind in tail),
    }
    if "moe" in pat and cfg.num_dense_layers:
        cache["dense_prefix"] = tuple(
            one("attn") for _ in range(cfg.num_dense_layers))
    return cache


def cache_scatter(big, sub, slots):
    """Insert the batch rows of a sub-batch serve cache into slots of a big
    cache: big[..., slots[i], ...] = sub[..., i, ...] for every state leaf.

    Leaves under "blocks" carry a leading layer-repetition axis (batch axis
    1); "tail"/"dense_prefix" leaves have batch axis 0.  Ring `positions`
    vectors are batch-shared and left untouched.  `slots` is an (n,) int32
    array; `sub` must come from `init_cache(cfg, n, max_len, ragged=True)`
    run through the same forward — identical structure, batch == n.
    """
    from repro.core.attention import KVCache

    def leaf(b, s, ax):
        idx = (slice(None),) * ax + (slots,)
        return b.at[idx].set(s)

    def visit(b, s, stacked):
        ax = 1 if stacked else 0
        if isinstance(b, KVCache):
            return KVCache(*[
                getattr(b, f) if f == "positions"
                else leaf(getattr(b, f), getattr(s, f), ax)
                for f in b._fields])
        if isinstance(b, dict):
            return {k: visit(v, s[k], stacked) for k, v in b.items()}
        if isinstance(b, (tuple, list)):
            return type(b)(visit(x, y, stacked) for x, y in zip(b, s))
        return leaf(b, s, ax)

    return {k: visit(v, sub[k], k == "blocks") for k, v in big.items()}


def cache_copy_pages(cache, src, dst):
    """Copy physical pages `src[i]` -> `dst[i]` in EVERY layer's paged pool.

    A slot's page-table row names the same physical page ids in every
    layer's pool, so one copy-on-write decision on the host applies to the
    whole stack: leaves under "blocks" carry a leading layer-repetition
    axis (page axis 1), "tail"/"dense_prefix" pools have page axis 0.
    Non-paged leaves are untouched (the tree may mix, e.g. future hybrid
    stacks); this is the device half of prefix sharing — see
    `attention.copy_pages`.
    """
    from repro.core.attention import PagedKVCache, copy_pages

    def visit(node, stacked):
        if isinstance(node, PagedKVCache):
            return copy_pages(node, src, dst, page_axis=1 if stacked else 0)
        if isinstance(node, dict):
            return {k: visit(v, stacked) for k, v in node.items()}
        if isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            return type(node)(visit(v, stacked) for v in node)
        return node

    return {k: visit(v, k == "blocks") for k, v in cache.items()}


def cache_fetch_pages(cache, pages):
    """Gather physical pages `pages[i]` out of EVERY layer's paged pool.

    Returns a tree with the same structure as `cache` where each
    `PagedKVCache` pool is replaced by a pool-shaped gather of the named
    pages (leaves under "blocks" keep their leading layer-repetition axis;
    page axis 1 there, 0 for "tail"/"dense_prefix").  Non-paged leaves map
    to None — the host half of page spill only moves KV pages.  One fetch
    covers the whole stack because a slot's page-table row names the same
    physical page ids in every layer's pool.
    """
    from repro.core.attention import PagedKVCache, fetch_pages

    def visit(node, stacked):
        if isinstance(node, PagedKVCache):
            return fetch_pages(node, pages, page_axis=1 if stacked else 0)
        if isinstance(node, dict):
            return {k: visit(v, stacked) for k, v in node.items()}
        if isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            return type(node)(visit(v, stacked) for v in node)
        return None

    return {k: visit(v, k == "blocks") for k, v in cache.items()}


def cache_page_checksums(cache, pages):
    """Per-page crc32 over EVERY layer's paged pool, chained in a fixed
    visit order (sorted dict keys, tuple order) so the checksum of page i
    covers the whole stack's bytes for that physical page.  Accepts the
    live cache (page ids) or a `cache_fetch_pages` host tree (positional
    indices; its None leaves are skipped).  Returns uint32[len(pages)].
    """
    import numpy as np

    from repro.core.attention import PagedKVCache, page_checksums

    crcs = np.zeros(len(pages), dtype=np.uint32)

    def visit(node, stacked):
        nonlocal crcs
        if isinstance(node, PagedKVCache):
            crcs = page_checksums(node, pages, page_axis=1 if stacked else 0,
                                  seeds=crcs)
        elif isinstance(node, dict):
            for k in sorted(node):
                visit(node[k], stacked)
        elif isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            for v in node:
                visit(v, stacked)

    for k in sorted(cache):
        visit(cache[k], k == "blocks")
    return crcs


def cache_restore_pages(cache, pages, data):
    """Scatter previously fetched pages back into EVERY layer's paged pool:
    pool page `pages[i]` := `data` page i — the inverse of
    `cache_fetch_pages` (same tree structure; None data leaves leave the
    cache leaf untouched).  Restoring into freshly allocated physical pages
    plus a rewritten page-table row reproduces the spilled slot's KV
    bit-identically across the whole stack in one device dispatch.
    """
    from repro.core.attention import PagedKVCache, restore_pages

    def visit(node, d, stacked):
        if isinstance(node, PagedKVCache):
            return restore_pages(node, pages, d, page_axis=1 if stacked else 0)
        if isinstance(node, dict):
            return {k: visit(v, d[k], stacked) for k, v in node.items()}
        if isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            return type(node)(visit(v, dv, stacked) for v, dv in zip(node, d))
        return node

    return {k: visit(v, data[k], k == "blocks") for k, v in cache.items()}


def forward_serve(params, batch: Dict[str, jax.Array], cache, offset,
                  cfg: ModelConfig, enc_out: Optional[jax.Array] = None,
                  seq_lens: Optional[jax.Array] = None,
                  pages: Optional[jax.Array] = None,
                  decode_rows: Optional[jax.Array] = None,
                  logit_positions: Optional[jax.Array] = None,
                  verify_len: int = 1, moe_load: bool = False):
    """One serve step (prefill chunk, single-token decode, or a MIXED batch
    of both).

    Ragged slot mode: `offset` may be a (B,) vector of per-slot positions and
    `seq_lens` a (B,) count of valid tokens per row (left-aligned padding
    beyond it is written to the cache but never advertised via `length`).
    Logits are then taken at each row's LAST VALID position instead of the
    shared final position.

    Paged slot mode: the cache tree holds `PagedKVCache` pools and `pages`
    is the shared (B, max_pages) page table — every attention layer writes
    and attends through the same table (one table row per slot names that
    slot's physical pages in every layer's pool).

    Mixed slot mode: `decode_rows` is a (B,) bool marking the rows of this
    step that carry exactly one decode token (the rest carry prefill
    chunks of up to the scheduler's token budget).  Every attention layer
    then routes each row class through the kernels its unchunked dispatch
    would use — one fused device program, per-row bit-identical to separate
    prefill and decode steps (see `blocks._mixed_attend`).  Only attention
    stacks support it (the same gate as the slot scheduler).

    Speculative verify mode: `verify_len` (static int) widens the decode
    row class to up to `verify_len` query tokens per row (current token +
    drafted continuations), and `logit_positions` — a (B, P) int32 matrix
    of in-step column indices — requests logits at ALL of a row's verify
    positions instead of only its last valid one; the return's logits leaf
    is then (B, P, V).  Per-column hidden states are position-wise
    identical to the single-token decode steps they replace, which is what
    makes draft acceptance exact.

    Returns (logits (B,V), new_cache, enc_out) — logits are (B, P, V) when
    `logit_positions` is given; enc_out is computed on the first
    (offset==0) call for encoder-decoder archs and threaded back.  With
    `moe_load` a fourth output follows: int32 (2,), the MoE blocks' load
    counts summed over layers (routed assignments of valid tokens that
    reached held experts, and (layer, held expert) pairs that received
    any; zeros for a stack without MoE blocks).
    """
    pat, R, tail = pattern_layout(cfg)
    x = _embed_inputs(params, batch, cfg, offset=offset)
    if cfg.is_encoder_decoder and enc_out is None:
        enc_out = encode(params, batch["frames"], cfg)

    new_cache = dict(cache)
    loads = []                      # MoE blocks' load counts

    def block(kind, p, x, st):
        out = _block_fwd_serve(kind, p, x, st, offset, cfg, enc_out=enc_out,
                               seq_lens=seq_lens, pages=pages,
                               decode_rows=decode_rows,
                               verify_len=verify_len)
        return out if kind == "moe" else out + (None,)

    if "dense_prefix" in cache:
        dp = []
        for p, st in zip(params["dense_prefix"], cache["dense_prefix"]):
            x, st, _ = block("attn", p, x, st)
            dp.append(st)
        new_cache["dense_prefix"] = tuple(dp)

    def scan_body(x, xs):
        group_params, group_state = xs
        new_states, group_loads = [], []
        for j, kind in enumerate(pat):
            x, st, load = block(kind, group_params[j], x, group_state[j])
            new_states.append(st)
            if load is not None:
                group_loads.append(load)
        if group_loads:
            return x, (tuple(new_states), sum(group_loads))
        return x, tuple(new_states)

    x, ys = jax.lax.scan(scan_body, x, (params["blocks"], cache["blocks"]))
    if "moe" in pat:
        ys, stack_loads = ys
        loads.append(jnp.sum(stack_loads, axis=0))
    new_cache["blocks"] = ys
    new_tail = []
    for i, kind in enumerate(tail):
        x, st, load = block(kind, params["tail"][i], x, cache["tail"][i])
        new_tail.append(st)
        if load is not None:
            loads.append(load)
    new_cache["tail"] = tuple(new_tail)

    def done(*out):
        if not moe_load:
            return out
        return out + (sum(loads) if loads else jnp.zeros((2,), jnp.int32),)

    if logit_positions is not None:
        # verify mode: logits at EVERY requested column — (B, P, V).
        # Per-position hidden states are position-wise, so column j equals
        # the single-position gather a plain decode step would have taken.
        idx = jnp.clip(jnp.asarray(logit_positions, jnp.int32),
                       0, x.shape[1] - 1)
        x = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        x = L.norm_apply(params["final_norm"], x, cfg.norm)
        head = params["embed"] if cfg.tie_embeddings else params["unembed"]
        return done(L.unembed_apply(head, x), new_cache, enc_out)
    if seq_lens is not None:
        # per-row last valid position (rows with seq_len == 0 read index 0;
        # their logits are garbage and the caller masks them out)
        idx = jnp.maximum(jnp.asarray(seq_lens, jnp.int32), 1) - 1
        x = jnp.take_along_axis(x, idx[:, None, None], axis=1)
    else:
        x = x[:, -1:]
    x = L.norm_apply(params["final_norm"], x, cfg.norm)
    head = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = L.unembed_apply(head, x)[:, 0]
    return done(logits, new_cache, enc_out)
