"""jit'd public wrappers for the Pallas kernels.

On a TPU backend these dispatch compiled Pallas (Mosaic); on any other
backend, which is the CPU test suite, they run in interpret mode.  Interpret
mode checks the kernel bodies and index maps but not what the chip refuses
(tiling, scoped VMEM): `tests/test_tpu_compile.py` compiles for a described
v5e, and `chip_smoke.py` calls the kernels with `interpret=False` and fails
without a TPU rather than falling back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import LUTSoftmaxConfig, PIMConfig
from repro.core import quant
from repro.core.attention import KVCache, PagedKVCache
from repro.kernels import pim_attention as _attn_k
from repro.kernels import pim_decode as _dec_k
from repro.kernels import pim_matmul as _mm_k
from repro.kernels import lut_softmax as _sm_k


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def pim_matmul(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    cfg: PIMConfig = PIMConfig(),
    out_dtype=jnp.bfloat16,
) -> jax.Array:
    """Kernel-backed PIM linear forward: quantize x, macro-tiled int matmul."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_scale = quant.symmetric_max_scale(x2, cfg.input_bits, axis=-1)
    x_q = quant.quantize(x2, x_scale, cfg.input_bits)
    y = _mm_k.pim_matmul_int_pallas(x_q, w_q, cfg, interpret=_interpret())
    y = y * x_scale * w_scale
    return y.reshape(lead + (w_q.shape[-1],)).astype(out_dtype)


def lut_softmax(
    scores_q: jax.Array,
    mask: jax.Array,
    cfg: LUTSoftmaxConfig = LUTSoftmaxConfig(),
) -> jax.Array:
    """Kernel-backed LUT softmax -> Q0.16 probability codes. Rows = leading dims."""
    lead = scores_q.shape[:-1]
    s2 = scores_q.reshape(-1, scores_q.shape[-1])
    m2 = jnp.broadcast_to(mask, scores_q.shape).reshape(s2.shape)
    codes = _sm_k.lut_softmax_pallas(s2, m2, cfg, interpret=_interpret())
    return codes.reshape(lead + (scores_q.shape[-1],))


def _q_kernel_layout(q: jax.Array, input_bits: int):
    """(B, Sq, H, Dh) float q -> head-major int8 (B*H, Sq, Dh) + scales."""
    B, Sq, H, Dh = q.shape
    q_scale = quant.symmetric_max_scale(q, input_bits, axis=-1)
    q_q = quant.quantize(q, q_scale, input_bits)
    q_q = q_q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dh)
    qs = q_scale[..., 0].transpose(0, 2, 1).reshape(B * H, Sq)
    return q_q, qs


def kernel_attention_layout(q: jax.Array, cache: KVCache,
                            input_bits: int = 8):
    """(B, Sq, H, Dh) float q + KVCache -> the flat head-major int8 operand
    layout the Pallas attention kernels take: (q_q, q_scale, k_q, k_scale,
    v_q, v_scale) with q rows (B*H, Sq, ...) and KV rows (B*Hkv, Sk, ...)
    ordered so that q row bh maps to KV row bh // q_per_kv.

    The KV last dim follows the cache's STORED width — `Dh` int8 bytes at
    kv_bits=8, `Dh/2` packed code bytes at 4 — which is how the kernels
    learn the precision (they infer kv_bits from the q/KV width ratio)."""
    B, Sq, H, Dh = q.shape
    _, Sk, Hkv, Dhk = cache.k_q.shape
    q_q, qs = _q_kernel_layout(q, input_bits)
    k_q = cache.k_q.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dhk)
    v_q = cache.v_q.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, Dhk)
    ks = cache.k_scale.transpose(0, 2, 1).reshape(B * Hkv, Sk)
    vs = cache.v_scale.transpose(0, 2, 1).reshape(B * Hkv, Sk)
    return q_q, qs, k_q, ks, v_q, vs


def paged_kernel_layout(pool: PagedKVCache):
    """(P, page_size, Hkv, Dh) pool -> the head-major page-pool layout the
    page-table-aware kernels take: (Hkv, P, page_size, Dh) K/V with
    (Hkv, P, page_size) scales.  Its ops carry the name scope `kv_relayout`,
    by which a profiler trace finds the relayout's device time."""
    with jax.named_scope("kv_relayout"):
        k_q = pool.k_q.transpose(2, 0, 1, 3)
        v_q = pool.v_q.transpose(2, 0, 1, 3)
        ks = pool.k_scale.transpose(2, 0, 1)
        vs = pool.v_scale.transpose(2, 0, 1)
        return k_q, ks, v_q, vs


@functools.partial(jax.jit, donate_argnums=(0,))
def paged_copy_pages(pool: PagedKVCache, src: jax.Array,
                     dst: jax.Array) -> PagedKVCache:
    """jit'd copy-on-write page copy over a single pool (donated): page
    `dst[i]` := page `src[i]` for K/V and both scale planes.  Layout-safe
    for the kernel path — `paged_kernel_layout` transposes at dispatch, so
    copying whole pages in canonical storage keeps both the behavioral
    gather view and the head-major kernel operands bit-identical."""
    from repro.core.attention import copy_pages
    return copy_pages(pool, src, dst)


@jax.jit
def paged_fetch_pages(pool: PagedKVCache, pages: jax.Array) -> PagedKVCache:
    """jit'd page fetch over a single pool: result page i is a bit-exact
    copy of pool page `pages[i]` (K/V + both scale planes) — the device
    half of spilling a victim slot's pages to host memory.  `pages` may
    contain repeated `TRASH_PAGE` padding entries so callers can keep the
    gather at power-of-two widths across recompiles."""
    from repro.core.attention import fetch_pages
    return fetch_pages(pool, pages)


@functools.partial(jax.jit, donate_argnums=(0,))
def paged_restore_pages(pool: PagedKVCache, pages: jax.Array,
                        data: PagedKVCache) -> PagedKVCache:
    """jit'd inverse of `paged_fetch_pages` (pool donated): pool page
    `pages[i]` := `data` page i.  Restoring spilled bytes into freshly
    allocated pages is layout-safe for the kernel path for the same reason
    `paged_copy_pages` is — `paged_kernel_layout` transposes at dispatch,
    so whole-page writes in canonical storage keep the behavioral gather
    view and the head-major kernel operands bit-identical."""
    from repro.core.attention import restore_pages
    return restore_pages(pool, pages, data)


def pim_flash_attention(
    q: jax.Array,              # (B, Sq, H, Dh) float
    cache: KVCache,
    q_offset,
    pim_cfg: PIMConfig = PIMConfig(),
    lut_cfg: LUTSoftmaxConfig = LUTSoftmaxConfig(),
    causal: bool = True,
    window: int = 0,
    out_dtype=jnp.bfloat16,
    decode_kernel: bool = True,
    decode_block_k: int = 256,
    q_len=None,
    force_decode_kernel: bool = False,
) -> jax.Array:
    """Fused flash-style PIM attention over the int8 KV cache.

    Single-token steps (Sq == 1) auto-dispatch to the split-K flash-decode
    kernel when `decode_kernel` is set — full grid occupancy across KV
    partitions instead of one padded q block serializing over the cache.
    `force_decode_kernel` extends that dispatch to Sq > 1: speculative
    VERIFY launches score each row's q_len drafted positions through the
    split-K grid, keeping every position bit-identical to the Sq == 1
    decode step it replaces (the auto-rule would pick the prefill kernel,
    whose numerics only match to rounding).

    `q_len` is the optional (B,) ragged-Q vector: row b's valid query count
    in this launch (rows past it early-out — see the kernels' docstrings).
    Rows with q_len == 0 cost zero KV iterations on either kernel.
    """
    B, Sq, H, Dh = q.shape
    q_q, qs, k_q, ks, v_q, vs = kernel_attention_layout(
        q, cache, pim_cfg.input_bits)
    if q_len is not None:
        q_len = jnp.asarray(q_len, jnp.int32)
    if decode_kernel and (Sq == 1 or force_decode_kernel):
        o = _dec_k.pim_decode_pallas(
            q_q, qs, k_q, ks, v_q, vs,
            jnp.asarray(q_offset, jnp.int32), cache.length,
            pim_cfg, lut_cfg, causal=causal, window=window,
            block_k=decode_block_k, interpret=_interpret(), q_len=q_len,
        )
    else:
        o = _attn_k.pim_attention_pallas(
            q_q, qs, k_q, ks, v_q, vs,
            jnp.asarray(q_offset, jnp.int32), cache.length,
            pim_cfg, lut_cfg, causal=causal, window=window,
            interpret=_interpret(), q_len=q_len,
        )
    return o.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3).astype(out_dtype)


def pim_paged_flash_attention(
    q: jax.Array,              # (B, Sq, H, Dh) float
    pool: PagedKVCache,
    page_table: jax.Array,     # (B, max_pages) int32, -1 = unallocated
    kv_len: jax.Array,         # (B,) int32 valid tokens per slot
    q_offset,                  # (B,) int32 absolute position of query 0
    pim_cfg: PIMConfig = PIMConfig(),
    lut_cfg: LUTSoftmaxConfig = LUTSoftmaxConfig(),
    causal: bool = True,
    out_dtype=jnp.bfloat16,
    decode_kernel: bool = True,
    q_len=None,
    force_decode_kernel: bool = False,
) -> jax.Array:
    """Fused PIM attention over the paged KV pool: both kernels walk the
    slot's page-table row instead of a contiguous cache.  Every KV
    partition of the decode kernel IS one page; a grid step of it fetches
    a block of the row's pages by async copies (see `pim_decode`).  The
    prefill kernel's KV axis runs over table entries, one page a step.
    Bit-identical to `pim_flash_attention` over a dense cache holding the
    same tokens with block_k == page_size.

    `q_len` is the optional (B,) ragged-Q vector (valid query rows per slot;
    0 = the row contributes nothing to this launch and costs zero compute).
    `force_decode_kernel` routes Sq > 1 speculative-verify launches through
    the split-K decode grid (see `pim_flash_attention`).

    Sliding-window layers are not paged (the scheduler gates them out), so
    there is no `window` parameter here.
    """
    B, Sq, H, Dh = q.shape
    q_q, qs = _q_kernel_layout(q, pim_cfg.input_bits)
    k_q, ks, v_q, vs = paged_kernel_layout(pool)
    if q_len is not None:
        q_len = jnp.asarray(q_len, jnp.int32)
    if decode_kernel and (Sq == 1 or force_decode_kernel):
        o = _dec_k.pim_decode_pallas(
            q_q, qs, k_q, ks, v_q, vs,
            jnp.asarray(q_offset, jnp.int32), jnp.asarray(kv_len, jnp.int32),
            pim_cfg, lut_cfg, causal=causal, interpret=_interpret(),
            page_table=page_table, q_len=q_len,
        )
    else:
        o = _attn_k.pim_attention_pallas(
            q_q, qs, k_q, ks, v_q, vs,
            jnp.asarray(q_offset, jnp.int32), jnp.asarray(kv_len, jnp.int32),
            pim_cfg, lut_cfg, causal=causal, interpret=_interpret(),
            page_table=page_table, q_len=q_len,
        )
    return o.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3).astype(out_dtype)
