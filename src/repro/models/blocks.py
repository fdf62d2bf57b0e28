"""Block library: every temporal-mixing block kind in the assigned arch pool.

Each kind implements the same protocol:
  init(key, cfg)                      -> params
  fwd_train(params, x, pos_ids, cfg)  -> x            (full-sequence, fp path)
  init_state(cfg, batch, max_len)     -> state        (serve-time state)
  fwd_serve(params, x, state, offset, cfg) -> (x, state)   (prefill & decode)

Kinds:
  attn        dense GQA attention + FFN          (all dense/moe/vlm archs)
  attn_local  sliding-window MQA + FFN           (recurrentgemma)
  moe         GQA attention + shared/routed MoE  (deepseek-moe, dbrx)
  mlstm       xLSTM matrix-memory block
  slstm       xLSTM scalar-memory block
  rglru       Griffin RG-LRU recurrent block + FFN
  xattn       decoder block w/ cross-attention   (whisper decoder)
  enc_attn    bidirectional encoder block        (whisper encoder)

Serve-path attention runs the paper's PIM pipeline (int8 KV + LUT softmax),
either the behavioral two-pass (`cfg.attn_impl == "behavioral"`) or the fused
Pallas kernel (`"kernel"`).  Train-path attention is fp (QAT: PIM linears with
straight-through gradients; see DESIGN.md §2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import attention as A
from repro.core import pim
from repro.models import layers as L
from repro.models.moe import moe_ffn_apply, moe_ffn_init, moe_ffn_serve


# ===========================================================================
# attention blocks
# ===========================================================================
def _attn_init(key, cfg: ModelConfig):
    d, dh = cfg.d_model, cfg.resolved_head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    keys = jax.random.split(key, 4)
    return {
        "wq": pim.pim_linear_init(keys[0], d, nq * dh, bias=cfg.qkv_bias),
        "wk": pim.pim_linear_init(keys[1], d, nkv * dh, bias=cfg.qkv_bias),
        "wv": pim.pim_linear_init(keys[2], d, nkv * dh, bias=cfg.qkv_bias),
        "wo": pim.pim_linear_init(keys[3], nq * dh, d),
    }


def _qkv(params, x, cfg: ModelConfig, pos_ids):
    from repro.runtime.sharding import constrain, dp_axes_spec
    B, S, _ = x.shape
    dh = cfg.resolved_head_dim
    p, en = cfg.pim, cfg.pim_linears
    ba = dp_axes_spec()
    q = pim.pim_linear_apply(params["wq"], x, p, en).reshape(B, S, cfg.num_heads, dh)
    k = pim.pim_linear_apply(params["wk"], x, p, en).reshape(B, S, cfg.num_kv_heads, dh)
    v = pim.pim_linear_apply(params["wv"], x, p, en).reshape(B, S, cfg.num_kv_heads, dh)
    # heads over the model axis (spatial Lego tiling: one head group per tile)
    q = constrain(q, ba, None, "model", None)
    k = constrain(k, ba, None, "model", None)
    v = constrain(v, ba, None, "model", None)
    if cfg.pos == "rope":
        q = L.rope_apply(q, pos_ids, cfg.rope_theta)
        k = L.rope_apply(k, pos_ids, cfg.rope_theta)
    return q, k, v


def attn_block_init(key, cfg: ModelConfig, window: int = 0, moe: bool = False,
                    cross: bool = False, causal: bool = True,
                    d_ff: Optional[int] = None):
    """`d_ff` sets the dense FFN's width (default `cfg.d_ff`)."""
    keys = jax.random.split(key, 5)
    p = {
        "norm1": L.norm_init(cfg.d_model, cfg.norm),
        "attn": _attn_init(keys[0], cfg),
        "norm2": L.norm_init(cfg.d_model, cfg.norm),
    }
    if moe:
        p["moe"] = moe_ffn_init(keys[1], cfg)
    else:
        p["mlp"] = L.mlp_init(keys[1], cfg, d_ff=d_ff)
    if cross:
        p["norm_x"] = L.norm_init(cfg.d_model, cfg.norm)
        p["xattn"] = _attn_init(keys[2], cfg)
    return p


def _ffn(params, x, cfg: ModelConfig):
    """Returns (y, aux_loss) — aux is the MoE load-balance term (0 for MLP)."""
    if "moe" in params:
        return moe_ffn_apply(params["moe"], x, cfg)
    return L.mlp_apply(params["mlp"], x, cfg), jnp.float32(0.0)


def attn_block_fwd_train(params, x, pos_ids, cfg: ModelConfig,
                         window: int = 0, causal: bool = True):
    h = L.norm_apply(params["norm1"], x, cfg.norm)
    q, k, v = _qkv(params["attn"], h, cfg, pos_ids)
    o = A.fp_attention(q, k, v, q_offset=0, causal=causal, window=window)
    B, S, _ = x.shape
    o = pim.pim_linear_apply(
        params["attn"]["wo"], o.reshape(B, S, -1), cfg.pim, cfg.pim_linears
    )
    x = x + o
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    y, aux = _ffn(params, h, cfg)
    return x + y, aux


def attn_block_init_state(cfg: ModelConfig, batch: int, max_len: int,
                          window: int = 0, ragged: bool = False,
                          page_size: int = 0, num_pages: int = 0):
    if page_size:
        if window:
            raise NotImplementedError(
                "paged KV does not support sliding-window (ring) layers")
        return A.init_paged_kv_cache(num_pages, page_size, cfg.num_kv_heads,
                                     cfg.resolved_head_dim,
                                     kv_bits=cfg.kv_bits)
    ring = bool(window) and max_len > window
    cache_len = min(max_len, window) if ring else max_len
    # ring caches stay int8 — `pim_attention_ring` reads raw int8 slots and
    # sliding windows cap the resident KV anyway, so sub-int8 buys little
    kv_bits = 8 if ring else cfg.kv_bits
    return A.init_kv_cache(batch, cache_len, cfg.num_kv_heads,
                           cfg.resolved_head_dim, ring=ring, ragged=ragged,
                           kv_bits=kv_bits)


def _serve_attend(q, cache, offset, cfg: ModelConfig, window: int, causal: bool,
                  q_len=None, force_decode_kernel: bool = False):
    if cfg.attn_impl == "kernel":
        from repro.kernels import ops
        # Sq == 1 steps dispatch to the flash-decode kernel (the GQA heads
        # packed into one tile) unless cfg.decode_kernel opts out.
        # `force_decode_kernel` keeps that dispatch for Sq > 1 speculative
        # VERIFY rows (bit-identity with the per-token decode launches).
        return ops.pim_flash_attention(
            q, cache, offset, cfg.pim, cfg.lut, causal=causal, window=window,
            out_dtype=jnp.dtype(cfg.compute_dtype),
            decode_kernel=cfg.decode_kernel,
            decode_block_k=cfg.decode_block_k,
            q_len=q_len,
            force_decode_kernel=force_decode_kernel,
        )
    # behavioral path: per-row two-pass arithmetic — rows past a caller's
    # q_len are garbage the caller already ignores, so no masking is needed
    return A.pim_attention(
        q, cache, cfg.pim, cfg.lut, q_offset=offset, causal=causal,
        window=window, out_dtype=jnp.dtype(cfg.compute_dtype),
    )


def _serve_attend_paged(q, pool, pages, kv_len, offset, cfg: ModelConfig,
                        causal: bool, q_len=None,
                        force_decode_kernel: bool = False):
    """Attend over the paged pool: the kernel path walks the page table in
    both Pallas kernels; the behavioral path runs the exact two-pass pipeline
    over a gathered slot-dense view (the bit-exact paged reference)."""
    if cfg.attn_impl == "kernel":
        from repro.kernels import ops
        return ops.pim_paged_flash_attention(
            q, pool, pages, kv_len, offset, cfg.pim, cfg.lut, causal=causal,
            out_dtype=jnp.dtype(cfg.compute_dtype),
            decode_kernel=cfg.decode_kernel,
            q_len=q_len,
            force_decode_kernel=force_decode_kernel,
        )
    dense = A.paged_gather(pool, pages, kv_len)
    return A.pim_attention(
        q, dense, cfg.pim, cfg.lut, q_offset=offset, causal=causal,
        out_dtype=jnp.dtype(cfg.compute_dtype),
    )


def _mixed_attend(q, cache, offset, kv_len, seq_lens, decode_rows,
                  cfg: ModelConfig, causal: bool, window: int = 0,
                  pages=None, verify_len: int = 1):
    """Mixed prefill+decode attention (kernel path): ONE device program, two
    early-out-complementary launches.

    The ragged-Q prefill launch serves the prefill-chunk rows (decode rows
    are masked to q_len 0 — zero KV iterations); the decode launch serves
    the decode rows through EXACTLY the dispatch an unchunked decode step
    uses (flash-decode kernel, or the prefill kernel when
    cfg.decode_kernel is off) with prefill rows masked to kv_len 0 — also
    zero compute.  Each row therefore pays only its own KV blocks AND
    produces the same bits it would produce in a separate unchunked
    prefill/decode dispatch, which is what keeps mixed scheduler steps
    bit-identical to the admit-then-decode baseline on the kernel path.

    `verify_len` (static, default 1) is the speculative-verify width: a
    decode row carries seq_lens[b] in [1, verify_len] query tokens (its
    current token plus drafted continuations) whose columns [0, seq_lens)
    all route through the decode launch — each position bit-identical to
    the Sq == 1 decode step a non-speculative scheduler would have run.
    """
    sl = jnp.asarray(seq_lens, jnp.int32)
    Lv = min(int(verify_len), q.shape[1])
    ql_prefill = jnp.where(decode_rows, 0, sl)
    ql_decode = jnp.where(decode_rows, jnp.minimum(sl, Lv), 0)
    kv_decode = jnp.where(decode_rows, kv_len, 0)
    if pages is not None:
        o = _serve_attend_paged(q, cache, pages, kv_len, offset, cfg, causal,
                                q_len=ql_prefill)
        od = _serve_attend_paged(q[:, :Lv], cache, pages, kv_decode, offset,
                                 cfg, causal, q_len=ql_decode,
                                 force_decode_kernel=True)
    else:
        o = _serve_attend(q, cache, offset, cfg, window, causal,
                          q_len=ql_prefill)
        od = _serve_attend(q[:, :Lv], cache._replace(length=kv_decode), offset,
                           cfg, window, causal, q_len=ql_decode,
                           force_decode_kernel=True)
    head = jnp.where(decode_rows[:, None, None, None], od, o[:, :Lv])
    return jnp.concatenate([head, o[:, Lv:]], axis=1)


def attn_block_fwd_serve(params, x, cache: A.KVCache, offset, cfg: ModelConfig,
                         window: int = 0, causal: bool = True, seq_lens=None,
                         pages=None, decode_rows=None, verify_len: int = 1):
    """Prefill (S>1, offset=0) or decode (S=1, offset=cache fill).

    Ragged slot mode: `offset` may be a (B,) vector of per-slot write
    positions, with `seq_lens` (B,) giving the VALID token count per row of
    this chunk (< S for left-aligned padded prefill rows, 0 for inactive
    slots).  K/V are scatter-written per slot and attention masks each row
    against its own length.  Sliding-window (ring) layers stay scalar-only.

    Paged slot mode: `cache` is a `PagedKVCache` pool and `pages` the
    (B, max_pages) page table — K/V scatter through the table into the
    slot's physical pages, attention walks the table, and each row's valid
    length is `offset + seq_lens` (or `offset + S`).  A prefix-shared tail
    prefill is the offset > 0 case: rows whose leading table entries map
    already-written (possibly refcount-shared) pages write only their tail
    tokens at positions [offset, offset + seq_lens) but attend over the
    full [0, offset + seq_lens) — the scheduler guarantees writes never
    land in a shared page (copy-on-write privatizes them first), so this
    path never needs to know about sharing.

    Mixed slot mode: `decode_rows` is a (B,) bool marking rows that
    contribute exactly ONE decode token to this step (their seq_lens is 1
    and their offset is the current fill); the remaining rows carry prefill
    chunks.  On the kernel path the two row classes dispatch through their
    unchunked kernels inside one program (`_mixed_attend`); the behavioral
    path needs no routing — its per-row arithmetic is already identical for
    any batch composition.

    Speculative verify mode: `verify_len` (static int > 1) widens the
    decode class — a decode row's seq_lens may be up to `verify_len`
    (current token + drafted continuations), all verified through the
    decode-kernel launch in one step.  The behavioral path again needs
    no routing (ragged per-row positions already cover it).

    Returns (x, cache); a MoE block returns (x, cache, load), the load
    counts of its dropless FFN over this step's valid tokens
    (`moe.moe_ffn_serve`).
    """
    B, S, _ = x.shape
    ragged = getattr(offset, "ndim", 0) >= 1
    h = L.norm_apply(params["norm1"], x, cfg.norm)
    pos_ids = (offset[:, None] + jnp.arange(S)[None, :] if ragged
               else offset + jnp.arange(S))
    q, k, v = _qkv(params["attn"], h, cfg, pos_ids)
    cache_len = cache.k_q.shape[1]   # dense buffer len (page_size if paged)
    if isinstance(cache, A.PagedKVCache):
        if pages is None:
            raise ValueError("paged serve step requires a page table")
        if window:
            raise NotImplementedError(
                "paged serving does not support sliding-window layers")
        offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (B,))
        cache = A.paged_cache_write(cache, k, v, offset, cfg.pim, pages,
                                    seq_lens)
        kv_len = offset + (S if seq_lens is None
                           else jnp.asarray(seq_lens, jnp.int32))
        if decode_rows is not None and cfg.attn_impl == "kernel":
            o = _mixed_attend(q, cache, offset, kv_len, seq_lens, decode_rows,
                              cfg, causal, pages=pages, verify_len=verify_len)
        else:
            o = _serve_attend_paged(q, cache, pages, kv_len, offset, cfg,
                                    causal, q_len=seq_lens)
    elif ragged:
        if window and cache_len == window:
            raise NotImplementedError(
                "ragged serving does not support ring (sliding-window) caches")
        cache = A.cache_write_ragged(cache, k, v, offset, cfg.pim, seq_lens)
        if decode_rows is not None and cfg.attn_impl == "kernel":
            o = _mixed_attend(q, cache, offset, cache.length, seq_lens,
                              decode_rows, cfg, causal, window=window,
                              verify_len=verify_len)
        else:
            o = _serve_attend(q, cache, offset, cfg, window, causal,
                              q_len=seq_lens)
    elif window and cache_len == window:
        if S > 1:
            # windowed prefill: banded attention within the chunk (single-chunk
            # prefill from position 0), then ring-write the last `window`
            # tokens for subsequent decode steps.
            tmp = A.init_kv_cache(B, S, cfg.num_kv_heads, cfg.resolved_head_dim)
            tmp = A.cache_write(tmp, k, v, 0, cfg.pim)
            o = _serve_attend(q, tmp, 0, cfg, window, causal)
            cache = A.cache_write_ring(cache, k, v, 0, cfg.pim)
        else:
            # decode: ring buffer, slot = absolute position mod window
            cache = A.cache_write_ring(cache, k, v, offset, cfg.pim)
            o = A.pim_attention_ring(q, cache, cfg.pim, cfg.lut, offset, window,
                                     out_dtype=jnp.dtype(cfg.compute_dtype))
    else:
        cache = A.cache_write(cache, k, v, offset, cfg.pim)
        o = _serve_attend(q, cache, offset, cfg, window, causal)
    o = pim.pim_linear_apply(
        params["attn"]["wo"], o.reshape(B, S, -1), cfg.pim, cfg.pim_linears
    )
    x = x + o
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    if "moe" in params:
        valid = (None if seq_lens is None else
                 jnp.arange(S)[None, :] < jnp.asarray(seq_lens)[:, None])
        y, load = moe_ffn_serve(params["moe"], h, cfg, valid)
        return x + y, cache, load
    return x + L.mlp_apply(params["mlp"], h, cfg), cache


# ===========================================================================
# cross-attention decoder block (whisper)
# ===========================================================================
def xattn_block_fwd_train(params, x, enc_out, pos_ids, cfg: ModelConfig):
    h = L.norm_apply(params["norm1"], x, cfg.norm)
    q, k, v = _qkv(params["attn"], h, cfg, pos_ids)
    o = A.fp_attention(q, k, v, q_offset=0, causal=True)
    B, S, _ = x.shape
    o = pim.pim_linear_apply(params["attn"]["wo"], o.reshape(B, S, -1),
                             cfg.pim, cfg.pim_linears)
    x = x + o
    # cross attention over encoder output (bidirectional)
    h = L.norm_apply(params["norm_x"], x, cfg.norm)
    dh = cfg.resolved_head_dim
    p, en = cfg.pim, cfg.pim_linears
    Se = enc_out.shape[1]
    qx = pim.pim_linear_apply(params["xattn"]["wq"], h, p, en
                              ).reshape(B, S, cfg.num_heads, dh)
    kx = pim.pim_linear_apply(params["xattn"]["wk"], enc_out, p, en
                              ).reshape(B, Se, cfg.num_kv_heads, dh)
    vx = pim.pim_linear_apply(params["xattn"]["wv"], enc_out, p, en
                              ).reshape(B, Se, cfg.num_kv_heads, dh)
    ox = A.fp_attention(qx, kx, vx, q_offset=0, causal=False)
    x = x + pim.pim_linear_apply(params["xattn"]["wo"], ox.reshape(B, S, -1), p, en)
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    y, aux = _ffn(params, h, cfg)
    return x + y, aux


def xattn_block_init_state(cfg: ModelConfig, batch: int, max_len: int):
    """Self-attn KV cache + cross-attn KV cache (written once at prefill)."""
    dh = cfg.resolved_head_dim
    return {
        "self": A.init_kv_cache(batch, max_len, cfg.num_kv_heads, dh),
        "cross": A.init_kv_cache(batch, max(cfg.encoder_seq_len, 1),
                                 cfg.num_kv_heads, dh),
    }


def xattn_block_fwd_serve(params, x, state, offset, cfg: ModelConfig,
                          enc_out=None):
    """Decoder serve step. On the first call (offset==0) enc_out must be given
    and the cross KV is written once — the paper's K-write dataflow."""
    B, S, _ = x.shape
    h = L.norm_apply(params["norm1"], x, cfg.norm)
    pos_ids = offset + jnp.arange(S)
    q, k, v = _qkv(params["attn"], h, cfg, pos_ids)
    self_cache = A.cache_write(state["self"], k, v, offset, cfg.pim)
    o = _serve_attend(q, self_cache, offset, cfg, 0, True)
    o = pim.pim_linear_apply(params["attn"]["wo"], o.reshape(B, S, -1),
                             cfg.pim, cfg.pim_linears)
    x = x + o
    cross_cache = state["cross"]
    if enc_out is not None:
        dh = cfg.resolved_head_dim
        Se = enc_out.shape[1]
        kx = pim.pim_linear_apply(params["xattn"]["wk"], enc_out, cfg.pim,
                                  cfg.pim_linears).reshape(B, Se, cfg.num_kv_heads, dh)
        vx = pim.pim_linear_apply(params["xattn"]["wv"], enc_out, cfg.pim,
                                  cfg.pim_linears).reshape(B, Se, cfg.num_kv_heads, dh)
        cross_cache = A.cache_write(cross_cache, kx, vx, 0, cfg.pim)
    h = L.norm_apply(params["norm_x"], x, cfg.norm)
    dh = cfg.resolved_head_dim
    qx = pim.pim_linear_apply(params["xattn"]["wq"], h, cfg.pim, cfg.pim_linears
                              ).reshape(B, S, cfg.num_heads, dh)
    ox = _serve_attend(qx, cross_cache, 0, cfg, 0, False)
    x = x + pim.pim_linear_apply(params["xattn"]["wo"], ox.reshape(B, S, -1),
                                 cfg.pim, cfg.pim_linears)
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    y, _ = _ffn(params, h, cfg)
    return x + y, {"self": self_cache, "cross": cross_cache}


# ===========================================================================
# mLSTM block (xLSTM) — matrix memory with exponential gating
# ===========================================================================
def mlstm_block_init(key, cfg: ModelConfig):
    d = cfg.d_model
    di = 2 * d  # up-projection factor 2 (xLSTM paper)
    dh = di // cfg.num_heads
    keys = jax.random.split(key, 8)
    return {
        "norm": L.norm_init(d, cfg.norm),
        "w_up": pim.pim_linear_init(keys[0], d, di),
        "w_gate": pim.pim_linear_init(keys[1], d, di),
        "wq": pim.pim_linear_init(keys[2], di, di),
        "wk": pim.pim_linear_init(keys[3], di, di),
        "wv": pim.pim_linear_init(keys[4], di, di),
        "w_igate": jnp.zeros((di, cfg.num_heads), jnp.float32),
        "w_fgate": jnp.zeros((di, cfg.num_heads), jnp.float32),
        "b_igate": jnp.zeros((cfg.num_heads,), jnp.float32),
        "b_fgate": jnp.full((cfg.num_heads,), 3.0, jnp.float32),
        "out_norm": L.norm_init(di, "rmsnorm"),
        "w_down": pim.pim_linear_init(keys[5], di, d),
    }


def _mlstm_qkv_gates(params, x, cfg: ModelConfig):
    B, S, _ = x.shape
    p, en = cfg.pim, cfg.pim_linears
    h = L.norm_apply(params["norm"], x, cfg.norm)
    u = pim.pim_linear_apply(params["w_up"], h, p, en)
    z = pim.pim_linear_apply(params["w_gate"], h, p, en)
    di = u.shape[-1]
    H = cfg.num_heads
    dh = di // H
    q = pim.pim_linear_apply(params["wq"], u, p, en).reshape(B, S, H, dh)
    k = pim.pim_linear_apply(params["wk"], u, p, en).reshape(B, S, H, dh)
    v = pim.pim_linear_apply(params["wv"], u, p, en).reshape(B, S, H, dh)
    uf = u.astype(jnp.float32)
    log_i = (uf @ params["w_igate"] + params["b_igate"])          # (B,S,H)
    log_f = -jax.nn.softplus(-(uf @ params["w_fgate"] + params["b_fgate"]))
    return u, z, q, k, v, log_i, log_f


_MLSTM_CHUNK = 1024   # chunk-scan carries (the (H, dh, dh) matrix memory)
                      # dominate backward storage: fewer, bigger chunks


def _mlstm_chunk_scan(q, k, v, log_i, log_f, state, chunk: int):
    """Chunkwise-stabilized mLSTM (linear-attention chunked form).

    q,k,v: (B,S,H,dh); log_i/log_f: (B,S,H).  state: {"C","n","m"}.
    Within-chunk quadratic + cross-chunk recurrent state — O(S * chunk)
    compute with O(dh^2) state, so 32k prefill never materializes SxS.
    Returns (h: (B,S,H,dh) f32, new_state).
    """
    B, S, H, dh = q.shape
    T = min(chunk, S)
    pad = (-S) % T
    if pad:
        # padded steps carry zero input gate -> no effect on state
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_i = jnp.pad(log_i, ((0, 0), (0, pad), (0, 0)),
                        constant_values=-1e30)
        log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // T

    def to_chunks(a):
        return jnp.moveaxis(
            a.reshape(B, nc, T, *a.shape[2:]), 1, 0
        )  # (nc, B, T, ...)

    # keep the full-sequence tensors in the compute dtype (bf16): the f32
    # upcast happens per chunk inside the scan body (memory: 56 GB -> <16 GB
    # on the xlstm train cell; see EXPERIMENTS.md §Perf extras)
    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    lic, lfc = to_chunks(log_i), to_chunks(log_f)

    def body(st, xs):
        qt, kt, vt, li, lf = xs                   # (B,T,H,dh) / (B,T,H)
        qt = qt.astype(jnp.float32)
        kt = kt.astype(jnp.float32) / (dh ** 0.5)
        vt = vt.astype(jnp.float32)
        F = jnp.cumsum(lf, axis=1)                # (B,T,H) inclusive
        # intra-chunk log weights L[t,s] = F_t - F_s + li_s   (s <= t)
        Lw = (F[:, :, None, :] - F[:, None, :, :] + li[:, None, :, :])
        Lw = Lw.transpose(0, 3, 1, 2)             # (B,H,T,T)
        causal = jnp.tril(jnp.ones((T, T), bool))
        Lw = jnp.where(causal[None, None], Lw, -jnp.inf)
        G = (F + st["m"][:, None]).transpose(0, 2, 1)            # (B,H,T)
        m_t = jnp.maximum(jnp.max(Lw, axis=-1), G)               # (B,H,T)
        D = jnp.exp(Lw - m_t[..., None])
        g = jnp.exp(G - m_t)                                     # (B,H,T)
        s = jnp.einsum("bqhd,bkhd->bhqk", qt, kt)
        w = s * D
        num = (jnp.einsum("bhqk,bkhd->bqhd", w, vt)
               + g.transpose(0, 2, 1)[..., None]
               * jnp.einsum("bqhd,bhde->bqhe", qt, st["C"]))
        den_s = (w.sum(-1) + g * jnp.einsum("bqhd,bhd->bhq", qt, st["n"]))
        den = jnp.maximum(jnp.abs(den_s), jnp.exp(-m_t)).transpose(0, 2, 1)
        h = num / den[..., None]                                 # (B,T,H,dh)
        # state update over the whole chunk
        F_T = F[:, -1]                                           # (B,H)
        lw_end = (F_T[:, None] - F + li)                         # (B,T,H)
        m_new = jnp.maximum(F_T + st["m"], jnp.max(lw_end, axis=1))
        c_old = jnp.exp(F_T + st["m"] - m_new)                   # (B,H)
        wts = jnp.exp(lw_end - m_new[:, None])                   # (B,T,H)
        C = (st["C"] * c_old[..., None, None]
             + jnp.einsum("bthd,bth,bthe->bhde", kt, wts, vt))
        n = st["n"] * c_old[..., None] + jnp.einsum("bthd,bth->bhd", kt, wts)
        return {"C": C, "n": n, "m": m_new}, h.astype(q.dtype)

    state, hs = jax.lax.scan(body, state, (qc, kc, vc, lic, lfc))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S + pad, H, dh)
    return h[:, :S], state


def mlstm_block_init_state(cfg: ModelConfig, batch: int, max_len: int):
    di = 2 * cfg.d_model
    H = cfg.num_heads
    dh = di // H
    return {
        "C": jnp.zeros((batch, H, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, H, dh), jnp.float32),
        "m": jnp.full((batch, H), -1e30, jnp.float32),
    }


def _mlstm_core(params, x, state, cfg: ModelConfig):
    B, S, _ = x.shape
    u, z, q, k, v, log_i, log_f = _mlstm_qkv_gates(params, x, cfg)
    h, state = _mlstm_chunk_scan(q, k, v, log_i, log_f, state, _MLSTM_CHUNK)
    hflat = h.reshape(B, S, -1).astype(x.dtype)
    hflat = L.norm_apply(params["out_norm"], hflat, "rmsnorm")
    out = hflat * jax.nn.silu(z)
    y = x + pim.pim_linear_apply(params["w_down"], out, cfg.pim, cfg.pim_linears)
    return y, state


def mlstm_block_fwd_train(params, x, pos_ids, cfg: ModelConfig):
    B = x.shape[0]
    y, _ = _mlstm_core(params, x, mlstm_block_init_state(cfg, B, 0), cfg)
    return y, jnp.float32(0.0)


def mlstm_block_fwd_serve(params, x, state, offset, cfg: ModelConfig):
    return _mlstm_core(params, x, state, cfg)


# ===========================================================================
# sLSTM block (xLSTM) — scalar memory, sequential recurrence
# ===========================================================================
def slstm_block_init(key, cfg: ModelConfig):
    d = cfg.d_model
    H = cfg.num_heads
    dh = d // H
    keys = jax.random.split(key, 6)
    def lin(k):
        return jax.random.normal(k, (d, d), jnp.float32) / (d ** 0.5)
    return {
        "norm": L.norm_init(d, cfg.norm),
        "w_z": lin(keys[0]), "w_i": lin(keys[1]),
        "w_f": lin(keys[2]), "w_o": lin(keys[3]),
        # block-diagonal recurrent weights, one (dh, dh) block per head
        "r_z": jnp.zeros((H, dh, dh), jnp.float32),
        "r_i": jnp.zeros((H, dh, dh), jnp.float32),
        "r_f": jnp.zeros((H, dh, dh), jnp.float32),
        "r_o": jnp.zeros((H, dh, dh), jnp.float32),
        "b_z": jnp.zeros((d,), jnp.float32),
        "b_i": jnp.zeros((d,), jnp.float32),
        "b_f": jnp.full((d,), 3.0, jnp.float32),
        "b_o": jnp.zeros((d,), jnp.float32),
        "norm2": L.norm_init(d, cfg.norm),
        "mlp": L.mlp_init(keys[4], cfg, d_ff=max(cfg.d_ff, 2 * d)),
    }


def slstm_block_init_state(cfg: ModelConfig, batch: int, max_len: int):
    d = cfg.d_model
    return {
        "c": jnp.zeros((batch, d), jnp.float32),
        "n": jnp.ones((batch, d), jnp.float32),
        "m": jnp.zeros((batch, d), jnp.float32),
        "h": jnp.zeros((batch, d), jnp.float32),
    }


def _slstm_scan(params, x, state, cfg: ModelConfig):
    B, S, d = x.shape
    H = cfg.num_heads
    dh = d // H
    xf = x.astype(jnp.float32)
    zx = xf @ params["w_z"] + params["b_z"]
    ix = xf @ params["w_i"] + params["b_i"]
    fx = xf @ params["w_f"] + params["b_f"]
    ox = xf @ params["w_o"] + params["b_o"]

    def rec(r, h):
        hh = h.reshape(B, H, dh)
        return jnp.einsum("bhd,hde->bhe", hh, r).reshape(B, d)

    def step(st, t):
        h = st["h"]
        z = jnp.tanh(zx[:, t] + rec(params["r_z"], h))
        lo_i = ix[:, t] + rec(params["r_i"], h)
        lo_f = fx[:, t] + rec(params["r_f"], h)
        o = jax.nn.sigmoid(ox[:, t] + rec(params["r_o"], h))
        log_f = -jax.nn.softplus(-lo_f)                # log sigmoid(f)
        m_new = jnp.maximum(log_f + st["m"], lo_i)
        i_ = jnp.exp(lo_i - m_new)
        f_ = jnp.exp(log_f + st["m"] - m_new)
        c = f_ * st["c"] + i_ * z
        n = jnp.maximum(f_ * st["n"] + i_, 1e-6)
        h_new = o * (c / n)
        return {"c": c, "n": n, "m": m_new, "h": h_new}, h_new

    state, hs = jax.lax.scan(step, state, jnp.arange(S))
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), state


def slstm_block_fwd_train(params, x, pos_ids, cfg: ModelConfig):
    h = L.norm_apply(params["norm"], x, cfg.norm)
    B = x.shape[0]
    y, _ = _slstm_scan(params, h, slstm_block_init_state(cfg, B, 0), cfg)
    x = x + y
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    return x + L.mlp_apply(params["mlp"], h, cfg), jnp.float32(0.0)


def slstm_block_fwd_serve(params, x, state, offset, cfg: ModelConfig):
    h = L.norm_apply(params["norm"], x, cfg.norm)
    y, state = _slstm_scan(params, h, state, cfg)
    x = x + y
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    return x + L.mlp_apply(params["mlp"], h, cfg), state


# ===========================================================================
# RG-LRU block (Griffin / recurrentgemma) — gated linear recurrence + FFN
# ===========================================================================
_RGLRU_C = 8.0


def rglru_block_init(key, cfg: ModelConfig):
    d = cfg.d_model
    w = cfg.lru_width or d
    keys = jax.random.split(key, 7)
    return {
        "norm": L.norm_init(d, cfg.norm),
        "w_x": pim.pim_linear_init(keys[0], d, w),
        "w_gate": pim.pim_linear_init(keys[1], d, w),
        "conv_w": jax.random.normal(keys[2], (cfg.conv1d_width, w), jnp.float32) * 0.1,
        "conv_b": jnp.zeros((w,), jnp.float32),
        "w_input_gate": jax.random.normal(keys[3], (w, w), jnp.float32) / (w ** 0.5),
        "w_rec_gate": jax.random.normal(keys[4], (w, w), jnp.float32) / (w ** 0.5),
        "lambda_p": jnp.full((w,), 4.0, jnp.float32),  # sigmoid(4) ~ 0.982
        "w_out": pim.pim_linear_init(keys[5], w, d),
        "norm2": L.norm_init(d, cfg.norm),
        "mlp": L.mlp_init(keys[6], cfg),
    }


def _rglru_gates(params, u):
    """u: (B,S,w) conv output (f32). Returns log_a, beta-scaled input.

    Griffin RG-LRU: a_t = sigmoid(Lambda)^(c * r_t) with c = 8, so
    log a_t = c * r_t * log sigmoid(Lambda)  (always <= 0).
    """
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uf @ params["w_rec_gate"])
    i = jax.nn.sigmoid(uf @ params["w_input_gate"])
    log_a = _RGLRU_C * r * jax.nn.log_sigmoid(params["lambda_p"])
    a2 = jnp.exp(2.0 * log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-8)) * (i * uf)
    return log_a, b


def _causal_conv1d(u, conv_w, conv_b, carry=None):
    """Depthwise causal conv. u: (B,S,w); carry: (B,W-1,w) history or None."""
    W = conv_w.shape[0]
    if carry is None:
        pad = jnp.zeros((u.shape[0], W - 1, u.shape[2]), u.dtype)
    else:
        pad = carry.astype(u.dtype)
    ext = jnp.concatenate([pad, u], axis=1)                       # (B,S+W-1,w)
    out = sum(ext[:, i:i + u.shape[1]] * conv_w[i] for i in range(W)) + conv_b
    new_carry = ext[:, -(W - 1):] if W > 1 else None
    return out.astype(u.dtype), new_carry


def _lru_scan(log_a, b, h0):
    """Associative scan of h_t = a_t h_{t-1} + b_t over axis 1."""
    def combine(x, y):
        (la1, b1), (la2, b2) = x, y
        return la1 + la2, b1 * jnp.exp(la2) + b2
    la, bb = jax.lax.associative_scan(combine, (log_a, b), axis=1)
    # fold initial state: h_t += exp(cumlog_a_t) * h0
    return bb + jnp.exp(la) * h0[:, None]


def rglru_block_fwd_train(params, x, pos_ids, cfg: ModelConfig):
    B, S, _ = x.shape
    w = cfg.lru_width or cfg.d_model
    h = L.norm_apply(params["norm"], x, cfg.norm)
    u = pim.pim_linear_apply(params["w_x"], h, cfg.pim, cfg.pim_linears)
    gate = jax.nn.gelu(
        pim.pim_linear_apply(params["w_gate"], h, cfg.pim, cfg.pim_linears))
    u, _ = _causal_conv1d(u, params["conv_w"], params["conv_b"])
    log_a, b = _rglru_gates(params, u)
    hseq = _lru_scan(log_a, b, jnp.zeros((B, w), jnp.float32))
    y = (hseq.astype(x.dtype) * gate)
    y = pim.pim_linear_apply(params["w_out"], y, cfg.pim, cfg.pim_linears)
    x = x + y
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    return x + L.mlp_apply(params["mlp"], h, cfg), jnp.float32(0.0)


def rglru_block_init_state(cfg: ModelConfig, batch: int, max_len: int):
    w = cfg.lru_width or cfg.d_model
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv1d_width - 1, w), jnp.float32),
    }


def rglru_block_fwd_serve(params, x, state, offset, cfg: ModelConfig):
    B, S, _ = x.shape
    h = L.norm_apply(params["norm"], x, cfg.norm)
    u = pim.pim_linear_apply(params["w_x"], h, cfg.pim, cfg.pim_linears)
    gate = jax.nn.gelu(
        pim.pim_linear_apply(params["w_gate"], h, cfg.pim, cfg.pim_linears))
    u, conv_carry = _causal_conv1d(u, params["conv_w"], params["conv_b"],
                                   carry=state["conv"])
    log_a, b = _rglru_gates(params, u)
    hseq = _lru_scan(log_a, b, state["h"])
    new_state = {"h": hseq[:, -1], "conv": conv_carry.astype(jnp.float32)}
    y = hseq.astype(x.dtype) * gate
    y = pim.pim_linear_apply(params["w_out"], y, cfg.pim, cfg.pim_linears)
    x = x + y
    h = L.norm_apply(params["norm2"], x, cfg.norm)
    return x + L.mlp_apply(params["mlp"], h, cfg), new_state
