"""Pallas TPU kernel: split-K flash decode for short-Sq PIM attention.

The prefill kernel (`pim_attention.py`) serializes over the KV axis per
(head, q-block) grid cell — fine for prefill where the q axis supplies
parallelism, but at decode (Sq == 1) it leaves the grid almost empty: one
padded q block per head, walking the whole cache sequentially.

This kernel restores occupancy the flash-decoding way, specialized to the
paper's integer dataflow:

  * **GQA head packing** — the `q_per_kv` query heads of a KV group are the
    sublane dimension of a single (G, Dh) q tile, so the Score matmul per KV
    block is one (G, Dh) x (Dh, bk) MXU call against the *raw* int8 cache
    (no head-expanded KV reads — decode streams Hkv, not H, caches).
    Speculative VERIFY rows (Sq == k+1 drafted positions) pack the extra
    queries into the same sublane dimension — row r = l*G + g is query
    position l of q head g, each with its own causal bound q_pos + l — so
    a multi-token verification is still one split-K launch per KV head,
    and row l's arithmetic is bit-identical to the Sq == 1 launch that a
    plain decode step at position q_pos + l would run (same per-row mask,
    same exact-zero contribution from masked lanes).
  * **Split-K grid** — grid (B*Hkv, ceil(Sk/block_k)): every KV partition is
    an independent grid cell emitting partial (m, denom, acc) in the LUT
    domain.  Partitions beyond `kv_len` (or outside causal/window reach of
    the single query) early-out via `pl.when` before any compute, so decode
    touches only ceil(kv_len/block_k) blocks regardless of the padded cache
    `max_len`.
  * **LUT-domain combine** — a second stage merges partials with rescale
    factors from the SAME 256-entry exp table (exp(-d*s) = table[d]/2^frac),
    exactly the arithmetic the online prefill kernel uses between blocks, so
    split-K numerics stay paper-faithful (within the usual LUT rounding).
  * **Paged KV walk** — with `page_table` set, K/V come from a global page
    pool (`(Hkv, P, page_size, Dh)` head-major layout) and every KV
    partition IS one page: the BlockSpec index map reads the slot's
    page-table row from SMEM (scalar prefetch) to turn the logical
    partition index into a physical page id, so the split-K grid walks
    scattered pages exactly as it walks a contiguous cache.  Unallocated
    entries (-1) early-out like out-of-length partitions: zero compute,
    and the combine treats them as empty (exact zero contribution), so
    paged output is bit-identical to the dense layout at block_k ==
    page_size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import LUTSoftmaxConfig, PIMConfig
from repro.core.lut_softmax import build_exp_table
from repro.core.quant import KV4_LEVELS
from repro.kernels.pim_attention import (_NEG, _block_needed, _kv4_dequant,
                                         _lut_exp)


def _decode_kernel(
    scalars_ref,                  # SMEM (3, nb): [q_pos_b, kv_len_b, q_len_b]
    pt_ref,                            # SMEM (nb, n_k_blocks) page table
    q_ref, qs_ref, k_ref, ks_ref, v_ref, vs_ref, table_ref, lv_ref,
    m_ref, den_ref, acc_ref, iters_ref, lut_ref, *deq_ref,
    block_k: int, r_pad: int, g: int, sq: int, causal: bool, window: int,
    sm_scale: float, score_scale: float, input_bits: int, hkv_per_b: int,
    kv_bits: int,
):
    ki = pl.program_id(1)
    # per-sequence scalars: each (b, hkv) grid row early-outs against ITS OWN
    # [q_pos, kv_len] — finished/empty slots (kv_len == 0) cost zero compute
    b = pl.program_id(0) // hkv_per_b
    q_pos = scalars_ref[0, b]       # absolute position of query row 0
    kv_len = scalars_ref[1, b]
    q_len = scalars_ref[2, b]       # valid query rows (<= sq) in this launch
    # unallocated pages (id < 0) can never contribute: their tokens are
    # beyond kv_len by the allocator invariant, and their VMEM block is a
    # clamped placeholder fetch — skip before any compute (dense callers
    # pass an all-zero dummy table, so this is a no-op there).  q_len_b == 0
    # marks a row that contributes no decode token to this launch (e.g. a
    # prefill-chunk row of a mixed batch, served by the ragged-Q prefill
    # kernel instead): zero partitions, exact-zero combine.  The partition
    # gate uses the LAST valid query's causal reach (q_pos + q_len - 1) —
    # the union of the per-row reaches below.
    q_hi = q_pos + jnp.minimum(q_len, sq) - 1
    needed = (pt_ref[b, ki] >= 0) & (q_len > 0) & _block_needed(
        ki * block_k, block_k, q_pos, q_hi, kv_len, causal, window)

    @pl.when(needed)
    def _body():
        iters_ref[...] = jnp.ones_like(iters_ref)
        q = q_ref[...].reshape(r_pad, q_ref.shape[-1])    # (R, Dh) int8
        if kv_bits == 4:
            # LUT-fused codebook dequant at the page load: exact int8-valued
            # f32 levels, so this f32 dot == the behavioral int32 einsum
            k = _kv4_dequant(k_ref, deq_ref[0],
                             lv_ref[...].astype(jnp.float32))  # (bk, Dh) f32
            s_int = jax.lax.dot_general(   # (R, bk) exact-integer f32
                q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            k = k_ref[...].reshape(block_k, k_ref.shape[-1])  # (bk, Dh) int8
            s_int = jax.lax.dot_general(   # (R, bk) int32 — the PIM Score engine
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32)
        qs = qs_ref[...].reshape(r_pad, 1)                # (R, 1) f32
        ks = ks_ref[...].reshape(1, block_k)              # (1, bk) f32
        s_real = s_int.astype(jnp.float32) * qs * ks * sm_scale

        qmax = float((1 << (input_bits - 1)) - 1)
        codes = jnp.clip(jnp.round(s_real / score_scale), -qmax - 1.0, qmax)

        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (r_pad, block_k), 1
        )
        # packed row r = l*G + g is query position q_pos + l of q head g:
        # each row masks against its OWN causal bound, so a verify row's
        # arithmetic is exactly the Sq == 1 launch at that position (rows
        # past q_len — including the sublane padding — are fully masked
        # and contribute exact zeros)
        l = jax.lax.broadcasted_iota(jnp.int32, (r_pad, block_k), 0) // g
        mask = (k_pos < kv_len) & (l < jnp.minimum(q_len, sq))
        if causal:
            mask &= k_pos <= q_pos + l
        if window:
            mask &= k_pos > q_pos + l - window
        codes = jnp.where(mask, codes, _NEG)

        table_f = table_ref[...].astype(jnp.float32)
        m = jnp.max(codes, axis=-1, keepdims=True)           # (R, 1)
        e = _lut_exp(codes, m, lut_ref, table_f)             # (R, bk)
        vs = vs_ref[...].reshape(block_k, 1)                 # (bk, 1) f32
        if kv_bits == 4:
            v_deq = _kv4_dequant(v_ref, deq_ref[0],
                                 lv_ref[...].astype(jnp.float32)) * vs
        else:
            v = v_ref[...].reshape(block_k, v_ref.shape[-1])  # (bk, Dh) int8
            v_deq = v.astype(jnp.float32) * vs
        acc = jax.lax.dot_general(     # (R, Dh)
            e, v_deq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
        m_ref[...] = m.reshape(m_ref.shape)
        den_ref[...] = jnp.sum(e, axis=-1).reshape(den_ref.shape)
        acc_ref[...] = acc[None, None]

    @pl.when(jnp.logical_not(needed))
    def _skip():
        iters_ref[...] = jnp.zeros_like(iters_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        den_ref[...] = jnp.zeros_like(den_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=(
        "pim_cfg", "lut_cfg", "causal", "window", "block_k", "interpret",
        "return_iters",
    ),
)
def pim_decode_pallas(
    q_q: jax.Array,        # (BH, Sq, Dh) int8 (Sq == 1, or k+1 verify rows)
    q_scale: jax.Array,    # (BH, Sq) float, cast to f32
    k_q: jax.Array,        # (BHkv, Sk, Dh) int8, or (Hkv, P, ps, Dh) paged
    k_scale: jax.Array,    # (BHkv, Sk) f32, or (Hkv, P, ps) paged
    v_q: jax.Array,        # like k_q
    v_scale: jax.Array,    # like k_scale
    q_offset: jax.Array,   # () or (B,) int32 — absolute position of the query
    kv_len: jax.Array,     # () or (B,) int32 — valid cache length per slot
    pim_cfg: PIMConfig = PIMConfig(),
    lut_cfg: LUTSoftmaxConfig = LUTSoftmaxConfig(),
    causal: bool = True,
    window: int = 0,
    block_k: int = 256,
    interpret: bool = False,
    return_iters: bool = False,
    page_table: jax.Array | None = None,   # (B, max_pages) int32, -1 = free
    q_len: jax.Array | None = None,        # () or (B,) int32, 0 = skip row
):
    """Split-K decode attention. Returns (BH, Sq, Dh) f32.

    `q_offset` / `kv_len` may be () scalars or (B,) per-slot vectors (ragged
    continuous batching): every (slot, kv-head, k-partition) grid cell
    early-outs against its own sequence length, so a retired/empty slot
    (kv_len == 0) executes zero KV partitions.

    `q_len` (default 1 everywhere) marks how many of a row's Sq query
    positions contribute to this launch: a row with q_len == 0 runs zero
    partitions and returns exact zeros — in a mixed prefill+decode step the
    prefill-chunk rows are masked out here and served by the ragged-Q
    prefill kernel in the same device program, while rows with q_len > 0
    stay bit-identical to an unmasked launch.

    Sq > 1 is the speculative-verify shape: slot b's queries sit at
    absolute positions q_offset_b .. q_offset_b + q_len_b - 1 (drafted
    continuation of its sequence), packed into the sublane dimension next
    to the GQA heads — so one launch scores all k+1 positions against the
    slot's full (possibly paged) KV, and each position's output is
    bit-identical to the Sq == 1 decode launch a non-speculative step
    would have run at that position.  Query rows past q_len_b are fully
    masked (exact-zero contribution, garbage output — callers slice).

    With `page_table` set, K/V operands are a page POOL in head-major layout
    (`(Hkv, num_pages, page_size, Dh)`, see `ops.paged_kernel_layout`) and
    each KV partition is one page of `page_table[b]` — `block_k` is forced
    to the page size and the partition count to the table width.  Slot b's
    logical partition ki reads physical page `page_table[b, ki]`; entries
    < 0 (unallocated) run zero compute and contribute exactly zero.

    With `return_iters=True` also returns the (BHkv, n_k_blocks) int32 map of
    KV partitions that actually ran (sum == blocks touched this token).
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

    if page_table is not None:
        Hkv, P, ps, _ = k_q.shape
        assert page_table.shape[0] == nb, (page_table.shape, nb)
        block_k = ps
        n_k_blocks = page_table.shape[1]
        BHkv = nb * Hkv
        pt = jnp.asarray(page_table, jnp.int32)
    else:
        BHkv, Sk, _ = k_q.shape
        pad_k = (-Sk) % block_k
        if pad_k:
            k_q = jnp.pad(k_q, ((0, 0), (0, pad_k), (0, 0)))
            v_q = jnp.pad(v_q, ((0, 0), (0, pad_k), (0, 0)))
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad_k)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad_k)))
        n_k_blocks = (Sk + pad_k) // block_k
        # dummy table (all allocated): the page guard in the kernel is a no-op
        pt = jnp.zeros((nb, n_k_blocks), jnp.int32)
    assert BH % BHkv == 0
    G = BH // BHkv
    R = Sq * G
    r_pad = max(8, ((R + 7) // 8) * 8)
    assert BHkv % nb == 0, (BHkv, nb)
    hkv_per_b = BHkv // nb

    # pack the q heads of each KV group — and, for verify launches, every
    # query position — into the sublane dimension: row r = l*G + g
    qg = (q_q.reshape(BHkv, G, Sq, Dh).transpose(0, 2, 1, 3)
          .reshape(BHkv, R, Dh))
    qsg = q_scale.reshape(BHkv, G, Sq).transpose(0, 2, 1).reshape(BHkv, R)
    if r_pad != R:
        qg = jnp.pad(qg, ((0, 0), (0, r_pad - R), (0, 0)))
        qsg = jnp.pad(qsg, ((0, 0), (0, r_pad - R)))
    grid = (BHkv, n_k_blocks)
    table, frac = build_exp_table(lut_cfg)

    kernel = functools.partial(
        _decode_kernel,
        block_k=block_k, r_pad=r_pad, g=G, sq=Sq, causal=causal,
        window=window,
        sm_scale=1.0 / (Dh ** 0.5), score_scale=lut_cfg.score_scale,
        input_bits=lut_cfg.input_bits, hkv_per_b=hkv_per_b, kv_bits=kv_bits,
    )
    levels = jnp.asarray(KV4_LEVELS, jnp.float32)            # (16,) codebook
    scalars = jnp.stack(
        [jnp.broadcast_to(q_off, (nb,)), jnp.broadcast_to(kvl, (nb,)),
         jnp.broadcast_to(ql, (nb,))]
    )                                                        # (3, nb)
    # (8, 128) block tiling: scales and the per-row partials m, den as
    # (1, n) lane rows, as in `pim_attention_pallas` (q scales cast to f32
    # there too)
    qsg = qsg.astype(jnp.float32)[:, None]
    k_scale = k_scale[..., None, :]
    v_scale = v_scale[..., None, :]
    if page_table is not None:
        # the index map turns the logical KV partition into a physical page:
        # clamped to the trash page for unallocated entries (the guarded
        # kernel body never reads the placeholder block)
        def page_index(b, k, s, t, h=hkv_per_b):
            return (jax.lax.rem(b, h), jnp.maximum(t[b // h, k], 0), 0, 0)
        kv_spec = pl.BlockSpec((1, 1, block_k, Dhk), page_index)
        scale_spec = pl.BlockSpec((1, 1, 1, block_k), page_index)
    else:
        kv_spec = pl.BlockSpec((1, block_k, Dhk), lambda b, k, s, t: (b, k, 0))
        scale_spec = pl.BlockSpec((1, 1, block_k),
                                  lambda b, k, s, t: (b, 0, k))
    part_spec = pl.BlockSpec((1, 1, 1, r_pad), lambda b, k, s, t: (b, k, 0, 0))
    part_m, part_den, part_acc, iters = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, r_pad, Dh), lambda b, k, s, t: (b, 0, 0)),
                pl.BlockSpec((1, 1, r_pad), lambda b, k, s, t: (b, 0, 0)),
                kv_spec,
                scale_spec,
                kv_spec,
                scale_spec,
                pl.BlockSpec((256,), lambda b, k, s, t: (0,)),
                pl.BlockSpec((16,), lambda b, k, s, t: (0,)),
            ],
            out_specs=[
                part_spec,
                part_spec,
                pl.BlockSpec((1, 1, r_pad, Dh), lambda b, k, s, t: (b, k, 0, 0)),
                pl.BlockSpec((1, 1, 1, 1), lambda b, k, s, t: (b, k, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((r_pad, block_k), jnp.float32),     # LUT exp
                # 4-bit only: the dequantized K, then V, page
                *([pltpu.VMEM((block_k, Dh), jnp.float32)]
                  if kv_bits == 4 else []),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, n_k_blocks, 1, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((BHkv, n_k_blocks, 1, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((BHkv, n_k_blocks, r_pad, Dh), jnp.float32),
            jax.ShapeDtypeStruct((BHkv, n_k_blocks, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(scalars, pt, qg, qsg, k_q, k_scale, v_q, v_scale, table, levels)
    part_m = part_m[:, :, 0]
    part_den = part_den[:, :, 0]
    iters = iters.reshape(BHkv, n_k_blocks)

    # ---- stage 2: combine partitions in the LUT domain ---------------------
    # Rescale each partition to the global max with exp(-d*s) = table[d]/2^frac
    # — the same arithmetic the online prefill kernel applies between blocks.
    # Skipped partitions (m == _NEG) get rescale 0: adding their exact-zero
    # partials never changes the f32 sums, which is what keeps paged (table-
    # width partitions) bit-identical to dense (ceil(Sk/bk) partitions).
    table_f = table.astype(jnp.float32)
    m_glob = jnp.max(part_m, axis=1, keepdims=True)          # (BHkv, 1, R)
    d = jnp.clip(m_glob - part_m, 0, 255).astype(jnp.int32)
    resc = jnp.take(table_f, d) / float(1 << frac)           # (BHkv, nb, R)
    resc = jnp.where(part_m <= _NEG / 2, 0.0, resc)
    den = jnp.sum(part_den * resc, axis=1)                   # (BHkv, R)
    acc = jnp.sum(part_acc * resc[..., None], axis=1)        # (BHkv, R, Dh)
    out = acc / jnp.maximum(den, 1.0)[..., None]
    out = (out[:, :R].reshape(BHkv, Sq, G, Dh).transpose(0, 2, 1, 3)
           .reshape(BH, Sq, Dh))
    if return_iters:
        return out, iters
    return out
