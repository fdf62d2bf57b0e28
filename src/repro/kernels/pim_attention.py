"""Pallas TPU kernel: fused flash-style PIM attention (beyond-paper).

The paper's dataflow materializes full score rows (2048x8-bit), ships them
through the DMA to the Softmax module, then back through a V-stationary PIM
for the AV product.  This kernel fuses Score -> LUT-Softmax -> AV into one
VMEM-resident streaming pass over KV blocks with *online* renormalization —
removing the O(S^2) score materialization while keeping the paper's numerics:

  * int8 Q, int8 PIM-resident KV cache (per-token scales),
  * scores requantized to 8-bit codes (the paper's 8-bit score port),
  * exp via the 256-entry LUT — realized as a one-hot x table matmul (a LUT
    *is* a crossbar read; on TPU the MXU plays the crossbar),
  * online rescale factors ALSO come from the same LUT (exp(-d*s) = table[d]),
    so the running renormalization stays within the paper's arithmetic.

Grid: (batch*heads, Sq/bq, Sk/bk), Sk innermost; running (max, denom, acc)
live in VMEM scratch.  GQA is handled by index-mapping KV blocks to
head-group bh // q_per_kv (no materialized KV expansion).

Grid pruning (beyond-paper perf): the scalar-prefetched (q_offset, kv_len)
let every (q-block, kv-block) grid cell decide whether it can contribute at
all — blocks entirely above the causal diagonal, beyond the valid cache
length, or outside the sliding window early-out via `pl.when` before any
MXU/VPU work.  Causal prefill therefore executes ~half the KV-block
iterations and decode against a max_len-sized cache touches only
ceil(kv_len/block_k) blocks.  Skipped blocks are bit-equivalent to computing
a fully-masked block (all-`_NEG` codes contribute e=0 and a LUT rescale
factor of exactly 1.0), so pruning changes iteration count, not numerics.
A per-(head, q-block) iteration counter is emitted alongside the output so
benchmarks and tests can assert the pruning actually happened.

Ragged-Q (mixed prefill+decode batches): the scalar-prefetched table is
(3, B) — [q_offset_b, kv_len_b, q_len_b] — and q blocks at or past a row's
`q_len_b` early-out entirely, so one launch serves rows contributing 1
decode token, a prefill chunk, or nothing at all, each walking only its own
KV blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import LUTSoftmaxConfig, PIMConfig
from repro.core.lut_softmax import build_exp_table
from repro.core.quant import KV4_LEVELS

_NEG = float(-(1 << 24))


def _lut_gather(d: jax.Array, table_f: jax.Array) -> jax.Array:
    """(r, c) int32 in [0,255] -> table values, as one-hot MXU matmul.

    HIGHEST precision keeps the read exact on the chip: the Q1.15 entries
    need 16 significant bits, and a single bf16 MXU pass keeps 8."""
    onehot = (d[..., None] == jnp.arange(256, dtype=jnp.int32)).astype(jnp.float32)
    return jax.lax.dot_general(
        onehot.reshape(-1, 256), table_f.reshape(256, 1),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).reshape(d.shape)


# score rows per LUT exp gather step in `_lut_exp`: one sublane tile, so
# the loop's row offset is tile-aligned at every block width
_LUT_ROWS = 8


def _lut_exp(codes: jax.Array, m: jax.Array, buf_ref,
             table_f: jax.Array) -> jax.Array:
    """Masked LUT exp of (r, c) score codes against the (r, 1) row max m.

    Masked scores hold exactly `_NEG` and get e = 0.  The table indices
    (-1 where masked) go through the (r, c) f32 scratch `buf_ref` in place,
    `_LUT_ROWS` rows per loop step, which bounds the live one-hot to
    (_LUT_ROWS * c, 256) (a whole 32 x 256 block overruns the 16 MB scoped
    VMEM of a v5e); the per-element arithmetic is that of one whole-block
    gather."""
    buf_ref[...] = jnp.where(codes > _NEG / 2, jnp.clip(m - codes, 0, 255),
                             -1.0)

    def step(i, carry):
        rows = pl.ds(pl.multiple_of(i * _LUT_ROWS, _LUT_ROWS), _LUT_ROWS)
        d = buf_ref[rows, :]
        buf_ref[rows, :] = jnp.where(
            d >= 0, _lut_gather(d.astype(jnp.int32), table_f), 0.0)
        return carry

    jax.lax.fori_loop(0, codes.shape[0] // _LUT_ROWS, step, 0)
    return buf_ref[...]


# KV rows dequantized per one-hot matmul in `_kv4_dequant`: the (rows * Dh,
# 16) f32 one-hot is lane-padded to 128 in VMEM (2 MB at 32 rows of Dh 128),
# so a whole 256-row block at once overruns the 16 MB scoped VMEM of a v5e
_KV4_ROWS = 32


def _kv4_dequant(src_ref, dst_ref, levels_f: jax.Array) -> jax.Array:
    """(..., r, Dh/2) int8 packed 4-bit KV block -> (r, Dh) f32 codebook
    values, written to the `dst_ref` scratch and returned.

    Nibble unpack (low half of the head dim in the low nibbles, high half in
    the high — `quant.pack_codes4`) followed by a 16-entry one-hot x table
    matmul: the same LUT-as-crossbar idiom the exp table uses, fused at the
    KV block load so no f32 (or even int8) KV plane is ever materialized in
    HBM.  The levels are int8-exact integers, so the f32 Score dot against
    an int8 q reproduces the behavioral int32 einsum exactly (|sum| <=
    256*128*127 < 2^24).  A loop over row chunks bounds the live one-hot to
    one chunk; the per-element arithmetic is that of one whole-block read."""
    rows = dst_ref.shape[0]
    n = math.gcd(rows, _KV4_ROWS)
    lead = (0,) * (src_ref.ndim - 2)

    def chunk(i, carry):
        lo = pl.multiple_of(i * n, n)
        p = src_ref[lead + (pl.ds(lo, n), slice(None))].astype(jnp.int32) & 0xFF
        codes = jnp.concatenate([p & 0xF, (p >> 4) & 0xF], axis=-1)
        onehot = (codes[..., None] == jnp.arange(16, dtype=jnp.int32)
                  ).astype(jnp.float32)
        dst_ref[pl.ds(lo, n), :] = jax.lax.dot_general(
            onehot.reshape(-1, 16), levels_f.reshape(16, 1),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        ).reshape(codes.shape)
        return carry

    jax.lax.fori_loop(0, rows // n, chunk, 0)
    return dst_ref[...]


def _online_reset(m_ref, den_ref, acc_ref):
    """The empty running state of `_online_fold`: max `_NEG`, sums 0."""
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    den_ref[...] = jnp.zeros_like(den_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _online_fold(m_ref, den_ref, acc_ref, codes, exp, lut, values,
                 frac_bits: int):
    """Fold a KV block into the running (max, denominator, acc) of its rows.

    `codes` are the block's (r, c) score codes, `_NEG` where masked;
    `exp(codes, m)` is their masked LUT exp against the (r, 1) max m, whose
    columns are the rows of the dequantized V that `values()` builds (it
    may drop padding lanes; built after the exp, so that V is not live
    across the exp's row loop).  The running sums move to the new max by a
    factor from the SAME LUT, exp(-d*s) = table[d] / 2^frac, read exactly
    by `lut` ((r, 1) int32 indices -> table values): 0 from the empty
    state, exactly 1.0 where the max does not move.  So a fully masked
    block (e = 0 against finite V) leaves the sums bit-unchanged, as if it
    were skipped."""
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(codes, axis=-1, keepdims=True))
    d = jnp.clip(m_new - m_old, 0, 255).astype(jnp.int32)
    resc = lut(d) / float(1 << frac_bits)
    resc = jnp.where(m_old <= _NEG / 2, jnp.zeros_like(resc), resc)
    e = exp(codes, m_new)
    den_ref[...] = den_ref[...] * resc + jnp.sum(e, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        e, values(), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    acc_ref[...] = acc_ref[...] * resc + pv
    m_ref[...] = m_new


def _online_result(den_ref, acc_ref):
    """The rows' attention output from the running sums: acc / denominator
    (exact zeros for rows that folded nothing in)."""
    return acc_ref[...] / jnp.maximum(den_ref[...], 1.0)


def _block_needed(k_start, block_k, q_lo, q_hi, kv_len, causal: bool,
                  window: int):
    """Can KV block [k_start, k_start+block_k) contribute to queries at
    absolute positions [q_lo, q_hi]?  All-False blocks are fully masked."""
    needed = k_start < kv_len
    if causal:
        needed &= k_start <= q_hi
    if window:
        needed &= (k_start + block_k - 1) > (q_lo - window)
    return needed


def _attn_kernel(
    scalars_ref,                  # SMEM (3, nb): [q_offset_b, kv_len_b, q_len_b]
    pt_ref,                            # SMEM (nb, n_k_blocks) page table
    q_ref, qs_ref, k_ref, ks_ref, v_ref, vs_ref, table_ref, lv_ref,
    out_ref, iters_ref,
    m_ref, denom_ref, acc_ref, lut_ref, *deq_ref,
    block_q: int, block_k: int, n_k_blocks: int, causal: bool,
    window: int, sm_scale: float, score_scale: float, input_bits: int,
    table_frac_bits: int, prune: bool, h_per_b: int,
    kv_bits: int,
):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _online_reset(m_ref, denom_ref, acc_ref)
        iters_ref[...] = jnp.zeros_like(iters_ref)

    # each grid row reads ITS sequence's [q_offset, kv_len, q_len] — ragged
    # batches prune/mask per sequence (h_per_b rows of the flat BH axis per
    # sequence), and q blocks past a row's q_len (padding rows of a ragged /
    # mixed prefill+decode batch) run ZERO KV iterations
    b = pl.program_id(0) // h_per_b
    q_offset = scalars_ref[0, b]
    kv_len = scalars_ref[1, b]
    q_len = scalars_ref[2, b]

    qi = pl.program_id(1)
    # an unallocated page (id < 0) is a clamped placeholder fetch and must be
    # skipped even with prune=False — its tokens are beyond kv_len by the
    # allocator invariant (dense callers pass an all-zero dummy table); a q
    # block entirely past q_len holds only padding rows whose output nobody
    # reads, so it is skipped under the same contract
    needed = (pt_ref[b, ki] >= 0) & (qi * block_q < q_len)
    if prune:
        # causal reach ends at the last VALID query row of this block (rows
        # past q_len are padding — skipping their KV blocks only zeroes
        # output the caller already ignores)
        needed &= _block_needed(
            ki * block_k, block_k,
            q_offset + qi * block_q,
            q_offset + jnp.minimum((qi + 1) * block_q, q_len) - 1,
            kv_len, causal, window,
        )

    @pl.when(needed)
    def _body():
        iters_ref[...] += 1
        q = q_ref[...][0]                  # (bq, Dh) int8
        if kv_bits == 4:
            # LUT-fused dequant at the block load: exact int8-valued f32
            # levels, so this f32 dot == the behavioral int32 einsum
            k = _kv4_dequant(k_ref, deq_ref[0],
                             lv_ref[...].astype(jnp.float32))  # (bk, Dh) f32
            s_int = jax.lax.dot_general(   # (bq, bk) exact-integer f32
                q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            k = k_ref[...].reshape(block_k, k_ref.shape[-1])  # (bk, Dh) int8
            s_int = jax.lax.dot_general(   # (bq, bk) int32 — the PIM Score engine
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
        qs = qs_ref[...].reshape(block_q, 1)        # (bq, 1) f32
        ks = ks_ref[...].reshape(1, block_k)        # (1, bk) f32
        s_real = s_int.astype(jnp.float32) * qs * ks * sm_scale

        # requantize to the 8-bit score port
        qmax = float((1 << (input_bits - 1)) - 1)
        codes = jnp.clip(jnp.round(s_real / score_scale), -qmax - 1.0, qmax)

        # position mask
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        codes = jnp.where(mask, codes, _NEG)

        def values():
            vs = vs_ref[...].reshape(block_k, 1)    # (bk, 1) f32
            if kv_bits == 4:
                return _kv4_dequant(v_ref, deq_ref[0],
                                    lv_ref[...].astype(jnp.float32)) * vs
            v = v_ref[...].reshape(block_k, v_ref.shape[-1])  # (bk, Dh) int8
            return v.astype(jnp.float32) * vs

        # online LUT softmax update
        table_f = table_ref[...].astype(jnp.float32)
        _online_fold(m_ref, denom_ref, acc_ref, codes,
                     lambda c, m: _lut_exp(c, m, lut_ref, table_f),
                     lambda d: _lut_gather(d, table_f), values,
                     table_frac_bits)

    @pl.when(ki == n_k_blocks - 1)
    def _flush():
        out_ref[...] = _online_result(denom_ref, acc_ref)[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "pim_cfg", "lut_cfg", "causal", "window",
        "block_q", "block_k", "interpret",
        "prune", "return_iters",
    ),
)
def pim_attention_pallas(
    q_q: jax.Array,        # (BH, Sq, Dh) int8
    q_scale: jax.Array,    # (BH, Sq) float, cast to f32
    k_q: jax.Array,        # (BHkv, Sk, Dh) int8, or (Hkv, P, ps, Dh) paged;
                           #   last dim Dh/2 when packed 4-bit (kv_bits=4)
    k_scale: jax.Array,    # (BHkv, Sk) f32, or (Hkv, P, ps) paged
    v_q: jax.Array,        # like k_q
    v_scale: jax.Array,    # like k_scale
    q_offset: jax.Array,   # () or (B,) int32 — absolute position of query 0
    kv_len: jax.Array,     # () or (B,) int32 — valid cache length per sequence
    pim_cfg: PIMConfig = PIMConfig(),
    lut_cfg: LUTSoftmaxConfig = LUTSoftmaxConfig(),
    causal: bool = True,
    window: int = 0,
    block_q: int = 32,
    block_k: int = 256,
    interpret: bool = False,
    prune: bool = True,
    return_iters: bool = False,
    page_table: jax.Array | None = None,   # (B, max_pages) int32, -1 = free
    q_len: jax.Array | None = None,        # () or (B,) int32 valid q rows
):
    """Fused PIM attention. Returns (BH, Sq, Dh) f32 (scales already applied).

    `q_offset` / `kv_len` may be () scalars (whole-batch) or (B,) vectors
    (ragged batch): every (head, q-block, kv-block) grid cell masks and
    early-outs against its OWN sequence's offset/length, so variable-length
    prefill packs without cross-contamination and empty rows cost zero
    KV-block iterations.

    `q_len` (default: all Sq rows valid) is the RAGGED-Q axis: row b's valid
    query count in this launch.  Whole q blocks at or past a row's q_len
    early-out before any compute (their output is zero), and the causal
    prune treats the row's last valid query as its reach — so a mixed
    prefill+decode batch packs decode rows (q_len 1), prefill-chunk rows
    (q_len up to the chunk budget) and idle rows (q_len 0, zero iterations)
    into ONE launch, each paying only its own KV blocks.  Rows below q_len
    are bit-identical to a q_len=None launch of the same rows.

    With `page_table` set, K/V operands are a page pool in head-major layout
    (`(Hkv, num_pages, page_size, Dh)`): the KV grid axis runs over the
    table width, `block_k` is forced to the page size, and each
    (head, q-block, kv-block) cell streams the physical page named by its
    slot's table row (scalar-prefetched SMEM read inside the BlockSpec
    index map).  Unallocated entries (-1) execute zero iterations — chunked
    ragged prefill over scattered pages is bit-identical to the dense
    layout at block_k == page_size.

    With `return_iters=True` also returns the (BH, n_q_blocks) int32 count of
    KV-block iterations each q-block actually executed (the grid-pruning
    probe: causal prefill ~halves it, decode sees ceil(kv_len/block_k)).

    Blockwise 4-bit KV is signalled by the storage layout (K/V last dim ==
    Dh/2): the kernel unpacks nibbles and dequantizes through the 16-entry
    dynamic-map codebook at the block load (`_kv4_dequant`) — no f32 or
    int8 KV plane is materialized, and since the codebook levels are exact
    int8 integers the f32 Score dot matches the behavioral int32 einsum.
    """
    BH, Sq, Dh = q_q.shape
    # stored KV width: Dh int8 bytes at kv_bits=8, Dh/2 packed bytes at 4 —
    # the storage layout is the kv_bits signal (static under jit)
    Dhk = k_q.shape[-1]
    kv_bits = 4 if Dhk * 2 == Dh else 8
    q_off = jnp.reshape(jnp.asarray(q_offset, jnp.int32), (-1,))
    kvl = jnp.reshape(jnp.asarray(kv_len, jnp.int32), (-1,))
    ql = jnp.reshape(jnp.asarray(Sq if q_len is None else q_len, jnp.int32),
                     (-1,))
    nb = max(q_off.shape[0], kvl.shape[0], ql.shape[0])
    assert BH % nb == 0, (BH, nb)
    if page_table is not None:
        Hkv, P, ps, _ = k_q.shape
        assert page_table.shape[0] == nb, (page_table.shape, nb)
        block_k = ps
        n_k_blocks = page_table.shape[1]
        q_per_kv = BH // (nb * Hkv)
        pt = jnp.asarray(page_table, jnp.int32)
    else:
        BHkv, Sk, _ = k_q.shape
        assert BH % BHkv == 0
        q_per_kv = BH // BHkv
        pad_k = (-Sk) % block_k
        if pad_k:
            k_q = jnp.pad(k_q, ((0, 0), (0, pad_k), (0, 0)))
            v_q = jnp.pad(v_q, ((0, 0), (0, pad_k), (0, 0)))
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_k)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_k)))
        n_k_blocks = (Sk + pad_k) // block_k
        pt = jnp.zeros((nb, n_k_blocks), jnp.int32)   # dummy: all allocated
    block_q = min(block_q, max(8, ((Sq + 7) // 8) * 8))
    pad_q = (-Sq) % block_q
    if pad_q:
        q_q = jnp.pad(q_q, ((0, 0), (0, pad_q), (0, 0)))
        q_scale = jnp.pad(q_scale, ((0, 0), (0, pad_q)))
    Sqp = Sq + pad_q
    grid = (BH, Sqp // block_q, n_k_blocks)
    table, frac = build_exp_table(lut_cfg)
    h_per_b = BH // nb

    kernel = functools.partial(
        _attn_kernel,
        block_q=block_q, block_k=block_k, n_k_blocks=grid[2],
        causal=causal, window=window,
        sm_scale=1.0 / (Dh ** 0.5), score_scale=lut_cfg.score_scale,
        input_bits=lut_cfg.input_bits, table_frac_bits=frac,
        prune=prune, h_per_b=h_per_b, kv_bits=kv_bits,
    )
    levels = jnp.asarray(KV4_LEVELS, jnp.float32)            # (16,) codebook
    scalars = jnp.stack(
        [jnp.broadcast_to(q_off, (nb,)), jnp.broadcast_to(kvl, (nb,)),
         jnp.broadcast_to(ql, (nb,))]
    )                                                        # (3, nb)
    # Mosaic tiles the last two dims of every block by (8, 128) unless a dim
    # spans its whole array, so each f32 scale block is a (1, n) lane row:
    # a singleton axis before the K/V token axis, and q scales split per q
    # block.  Rows keep the planes compact in HBM (a trailing singleton
    # axis would pad every entry to 128 lanes); the kernel reshapes the q
    # and V rows into the columns that scale its rows.  The q scales come
    # in the model's dtype (bf16 when serving), and Mosaic reshapes a bf16
    # row into a column only after the exact cast to f32.
    q_scale = q_scale.astype(jnp.float32).reshape(
        BH, Sqp // block_q, 1, block_q)
    k_scale = k_scale[..., None, :]
    v_scale = v_scale[..., None, :]
    if page_table is not None:
        # flat q row b*H + h attends kv head (b*H + h) // q_per_kv; its page
        # pool row is that modulo Hkv, and the page comes from the slot's
        # scalar-prefetched table (clamped to the trash page when -1 — the
        # guarded body never reads the placeholder)
        def page_index(b, i, k, s, t, qpk=q_per_kv, hk=Hkv, hb=h_per_b):
            return (jax.lax.rem(b // qpk, hk),
                    jnp.maximum(t[b // hb, k], 0), 0, 0)
        kv_spec = pl.BlockSpec((1, 1, block_k, Dhk), page_index)
        scale_spec = pl.BlockSpec((1, 1, 1, block_k), page_index)
    else:
        kv_spec = pl.BlockSpec(
            (1, block_k, Dhk),
            lambda b, i, k, s, t, qpk=q_per_kv: (b // qpk, k, 0),
        )
        scale_spec = pl.BlockSpec(
            (1, 1, block_k),
            lambda b, i, k, s, t, qpk=q_per_kv: (b // qpk, 0, k),
        )
    out, iters = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, Dh), lambda b, i, k, s, t: (b, i, 0)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda b, i, k, s, t: (b, i, 0, 0)),
                kv_spec,
                scale_spec,
                kv_spec,
                scale_spec,
                pl.BlockSpec((256,), lambda b, i, k, s, t: (0,)),
                pl.BlockSpec((16,), lambda b, i, k, s, t: (0,)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, Dh), lambda b, i, k, s, t: (b, i, 0)),
                pl.BlockSpec((1, 1, 1, 1),
                             lambda b, i, k, s, t: (b, i, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, Dh), jnp.float32),
                pltpu.VMEM((block_q, block_k), jnp.float32),   # LUT exp
                # 4-bit only: the dequantized K, then V, block
                *([pltpu.VMEM((block_k, Dh), jnp.float32)]
                  if kv_bits == 4 else []),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sqp, Dh), jnp.float32),
            jax.ShapeDtypeStruct((BH, Sqp // block_q, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(scalars, pt, q_q, q_scale, k_q, k_scale, v_q, v_scale, table, levels)
    iters = iters.reshape(BH, Sqp // block_q)
    out = out[:, :Sq]
    if return_iters:
        return out, iters
    return out
