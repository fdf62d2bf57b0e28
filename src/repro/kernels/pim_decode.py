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
    partition IS one page of the slot's page-table row (read from SMEM by
    scalar prefetch), so the split-K grid walks scattered pages exactly as
    it walks a contiguous cache.  For 8-bit pools whose pages tile a
    128-lane row, a grid step covers a block of
    `_PAGED_BLOCK_TOKENS // page_size` consecutive table entries: it
    fetches the block's pages by async copies into a double-buffered VMEM
    scratch (the next block's copies in flight while this one computes)
    and scores them as one lane row — one Score matmul, and the exp table
    read by two exact lane gathers — while each page's max, denominator
    and e @ v stay its own.  Other pools (4-bit pages of Dh/2 bytes,
    which Mosaic does not copy page by page) take one page per grid step,
    fetched by the BlockSpec index map.  Unallocated entries (-1) are
    never read and early-out like out-of-length partitions: zero compute,
    and the combine treats them as empty (exact zero contribution), so
    paged output is bit-identical to the dense layout at block_k ==
    page_size, on either walk.
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


# Tokens a grid step of the paged walk covers: a block of
# _PAGED_BLOCK_TOKENS // page_size consecutive page-table entries, scored
# as one row of _LANES lanes (at most that many tokens)
_PAGED_BLOCK_TOKENS = 128
_LANES = 128


def _masked_codes(s, qs, ks, k_pos, q_pos, kv_len, q_len, *, g: int,
                  causal: bool, window: int, sm_scale: float,
                  score_scale: float, input_bits: int):
    """(R, n) exact-integer f32 scores -> int8-range score codes, `_NEG`
    where masked.  `qs` (R, 1) and `ks` (1, n) are the q and K scales,
    `k_pos` the (R, n) absolute key positions."""
    s_real = s * qs * ks * sm_scale
    qmax = float((1 << (input_bits - 1)) - 1)
    codes = jnp.clip(jnp.round(s_real / score_scale), -qmax - 1.0, qmax)
    # packed row r = l*G + g is query position q_pos + l of q head g:
    # each row masks against its OWN causal bound, so a verify row's
    # arithmetic is exactly the Sq == 1 launch at that position (rows
    # past q_len — including the sublane padding — are fully masked
    # and contribute exact zeros)
    l = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
    mask = (k_pos < kv_len) & (l < q_len)
    if causal:
        mask &= k_pos <= q_pos + l
    if window:
        mask &= k_pos > q_pos + l - window
    return jnp.where(mask, codes, _NEG)


def _value_partials(e, v_deq):
    """The partition's LUT-domain denominator (R,) and e @ v (R, Dh)."""
    acc = jax.lax.dot_general(
        e, v_deq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.sum(e, axis=-1), acc


def _row_scalars(scalars_ref, hkv_per_b: int, sq: int):
    """The grid row's slot b, its [q_pos, kv_len, q_len] (q_len capped at
    sq) and the last valid query's position.  Each (b, hkv) grid row
    early-outs against ITS OWN sequence: finished/empty slots (kv_len == 0)
    cost zero compute."""
    b = pl.program_id(0) // hkv_per_b
    q_pos = scalars_ref[0, b]       # absolute position of query row 0
    kv_len = scalars_ref[1, b]
    q_len = jnp.minimum(scalars_ref[2, b], sq)  # valid query rows
    return b, q_pos, kv_len, q_len, q_pos + q_len - 1


def _partition_needed(pt_ref, b, e, q_pos, q_hi, kv_len, q_len, *,
                      block_k: int, causal: bool, window: int):
    """Does table entry (partition) e of slot b contribute?

    Unallocated pages (id < 0) never do: their tokens are beyond kv_len by
    the allocator invariant (dense callers pass an all-zero dummy table, so
    this is a no-op there).  q_len == 0 marks a row that contributes no
    decode token to this launch (e.g. a prefill-chunk row of a mixed batch,
    served by the ragged-Q prefill kernel instead): zero partitions,
    exact-zero combine.  The gate uses the LAST valid query's causal reach
    q_hi — the union of the per-row reaches."""
    return (pt_ref[b, e] >= 0) & (q_len > 0) & _block_needed(
        e * block_k, block_k, q_pos, q_hi, kv_len, causal, window)


def _decode_page_kernel(
    scalars_ref,                  # SMEM (3, nb): [q_pos_b, kv_len_b, q_len_b]
    pt_ref,                            # SMEM (nb, n_k_blocks) page table
    q_ref, qs_ref, k_ref, ks_ref, v_ref, vs_ref, table_ref, lv_ref,
    m_ref, den_ref, acc_ref, iters_ref, lut_ref, *deq_ref,
    hkv_per_b: int, sq: int, block_k: int, r_pad: int, kv_bits: int,
    **codes_kw,
):
    """One KV partition per grid step, fetched by its BlockSpec: the dense
    cache, and page pools the block walk does not take.  An unallocated
    page's block is a clamped placeholder fetch the gate keeps unread; a
    partition not needed writes the empty partial (`_NEG`, 0, 0)."""
    ki = pl.program_id(1)
    b, q_pos, kv_len, q_len, q_hi = _row_scalars(scalars_ref, hkv_per_b, sq)
    needed = _partition_needed(pt_ref, b, ki, q_pos, q_hi, kv_len, q_len,
                               block_k=block_k, causal=codes_kw["causal"],
                               window=codes_kw["window"])

    @pl.when(needed)
    def _body():
        iters_ref[...] = jnp.ones_like(iters_ref)
        q = q_ref[...].reshape(r_pad, q_ref.shape[-1])    # (R, Dh) int8
        if kv_bits == 4:
            # LUT-fused codebook dequant at the page load: exact int8-valued
            # f32 levels, so this f32 dot == the behavioral int32 einsum
            k = _kv4_dequant(k_ref, deq_ref[0],
                             lv_ref[...].astype(jnp.float32))  # (bk, Dh) f32
            s = jax.lax.dot_general(       # (R, bk) exact-integer f32
                q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            k = k_ref[...].reshape(block_k, k_ref.shape[-1])  # (bk, Dh) int8
            s = jax.lax.dot_general(       # (R, bk) int32 — the PIM Score engine
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32).astype(jnp.float32)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (r_pad, block_k), 1)
        codes = _masked_codes(
            s, qs_ref[...].reshape(r_pad, 1), ks_ref[...].reshape(1, block_k),
            k_pos, q_pos, kv_len, q_len, **codes_kw)

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
        den, acc = _value_partials(e, v_deq)
        m_ref[...] = m.reshape(m_ref.shape)
        den_ref[...] = den.reshape(den_ref.shape)
        acc_ref[...] = acc[None, None]

    @pl.when(jnp.logical_not(needed))
    def _skip():
        _write_empty(m_ref, den_ref, acc_ref, iters_ref)


def _write_empty(m_ref, den_ref, acc_ref, iters_ref):
    """The empty partial (`_NEG`, 0, 0) and no iteration, in every
    partition of the output blocks."""
    iters_ref[...] = jnp.zeros_like(iters_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    den_ref[...] = jnp.zeros_like(den_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _decode_block_kernel(
    scalars_ref,                  # SMEM (3, nb): [q_pos_b, kv_len_b, q_len_b]
    pt_ref,                   # SMEM (nb, n_blocks * ppb) padded page table
    q_ref, qs_ref,
    k_hbm, ks_hbm, v_hbm, vs_hbm,   # HBM pools, scales as 128-lane rows
    table_ref,                      # exp table as (2, 128)
    m_ref, den_ref, acc_ref, iters_ref,  # (1, ppb, ...) partial blocks
    k_buf, ks_buf, v_buf, vs_buf, sem,
    hkv_per_b: int, sq: int, ppb: int, n_blocks: int, block_k: int,
    r_pad: int, **codes_kw,
):
    """A block of `ppb` consecutive table entries per grid step.

    The block's pages arrive by async copies into a double-buffered VMEM
    scratch: step ki starts the copies of block ki+1 of its grid row, then
    waits for block ki (a row's first block is started in its own step),
    so the KV axis runs in order.  Only pages the partition gate admits
    are copied.  The block's tokens are scored as one 128-lane row: one
    Score matmul, the codes, and the exp table read by lane gathers, for
    all its pages at once.  Each page stays one split-K partition:
    its max, denominator and e @ v are taken over its own lanes by the
    same operations as the one-page walk, so the partials are
    bit-identical to it.  A page not copied leaves stale bytes in its
    buffer slot; they reach only its own lanes, and its outputs are the
    empty partial."""
    ki = pl.program_id(1)
    h = jax.lax.rem(pl.program_id(0), hkv_per_b)
    b, q_pos, kv_len, q_len, q_hi = _row_scalars(scalars_ref, hkv_per_b, sq)
    ps = block_k

    def block_copies(blk, slot):
        """(gate, copies) per page of table block blk, into buffer slot."""
        for j in range(ppb):
            e = blk * ppb + j
            gate = _partition_needed(
                pt_ref, b, e, q_pos, q_hi, kv_len, q_len, block_k=ps,
                causal=codes_kw["causal"], window=codes_kw["window"])
            page = jnp.maximum(pt_ref[b, e], 0)
            yield gate, [
                pltpu.make_async_copy(src.at[h, page], dst.at[slot, j],
                                      sem.at[slot])
                for src, dst in ((k_hbm, k_buf), (ks_hbm, ks_buf),
                                 (v_hbm, v_buf), (vs_hbm, vs_buf))]

    def start(blk, slot):
        for gate, copies in block_copies(blk, slot):
            @pl.when(gate)
            def _():
                for c in copies:
                    c.start()

    slot = jax.lax.rem(ki, 2)

    @pl.when(ki == 0)
    def _first():
        start(0, 0)

    @pl.when(ki + 1 < n_blocks)
    def _next():
        start(ki + 1, 1 - slot)

    gates = []
    for gate, copies in block_copies(ki, slot):
        gates.append(gate)

        @pl.when(gate)
        def _():
            for c in copies:
                c.wait()

    any_needed = functools.reduce(jnp.logical_or, gates)

    @pl.when(any_needed)
    def _block():
        q = q_ref[...].reshape(r_pad, q_ref.shape[-1])    # (R, Dh) int8
        qs = qs_ref[...].reshape(r_pad, 1)
        tab = table_ref[...].astype(jnp.float32)          # (2, 128)
        lo = jnp.broadcast_to(tab[0:1], (r_pad, _LANES))
        hi = jnp.broadcast_to(tab[1:2], (r_pad, _LANES))
        lane = jax.lax.broadcasted_iota(jnp.int32, (r_pad, _LANES), 1)
        row_lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

        def lanes_of(x, j):
            """Page j's lanes [j*ps, (j+1)*ps) of x, moved to lane 0."""
            return (pltpu.roll(x, _LANES - j * ps, 1) if j else x)[:, :ps]

        # the block's K as (n, Dh) bf16 (int8 values are exact in it), in
        # one lane row of keys
        n = ppb * ps
        kc = jnp.concatenate([k_buf[slot, j].astype(jnp.bfloat16)
                              for j in range(ppb)])
        if n < _LANES:
            kc = jnp.concatenate(
                [kc, jnp.zeros((_LANES - n, kc.shape[1]), kc.dtype)])
        # one Score matmul for the block: every product and partial sum is
        # an exact integer below 2**24, as in the int32 dot
        s = jax.lax.dot_general(
            q.astype(jnp.bfloat16), kc, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (R, 128)
        # the pages' K scales side by side in one lane row
        ks = ks_buf[slot, 0]                                # (1, 128)
        for j in range(1, ppb):
            ks = jnp.where(row_lane // ps == j,
                           pltpu.roll(ks_buf[slot, j], j * ps, 1), ks)
        codes = _masked_codes(s, qs, ks, ki * n + lane, q_pos, kv_len,
                              q_len, **codes_kw)
        if n < _LANES:
            codes = jnp.where(lane < n, codes, _NEG)
        # each page's max over its own lanes, then the table index against
        # it: per element the one-page walk's arithmetic
        ms = [jnp.max(lanes_of(codes, j), axis=-1, keepdims=True)
              for j in range(ppb)]
        m_lanes = jnp.zeros_like(codes)
        for j, m in enumerate(ms):
            m_lanes = jnp.where(lane // ps == j, m, m_lanes)
        d = jnp.where(codes > _NEG / 2, jnp.clip(m_lanes - codes, 0, 255),
                      -1.0)
        # the exp table (256 entries) read by two lane gathers: exact
        idx = jnp.maximum(d, 0.0).astype(jnp.int32)
        low = idx & (_LANES - 1)
        e = jnp.where(
            idx < _LANES,
            jnp.take_along_axis(lo, low, 1, mode="promise_in_bounds"),
            jnp.take_along_axis(hi, low, 1, mode="promise_in_bounds"))
        e = jnp.where(d >= 0, e, 0.0)
        for j, gate in enumerate(gates):
            vs = vs_buf[slot, j][:, :ps].reshape(ps, 1)       # (ps, 1)
            v_deq = v_buf[slot, j].astype(jnp.float32) * vs
            den, acc = _value_partials(lanes_of(e, j), v_deq)
            m_ref[0, j] = jnp.where(gate, ms[j].reshape(1, r_pad), _NEG)
            den_ref[0, j] = jnp.where(gate, den.reshape(1, r_pad), 0.0)
            acc_ref[0, j] = jnp.where(gate, acc, 0.0)
            iters_ref[0, j] = jnp.broadcast_to(gate.astype(jnp.int32),
                                               (1, 1))

    @pl.when(jnp.logical_not(any_needed))
    def _skip():
        _write_empty(m_ref, den_ref, acc_ref, iters_ref)


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
    < 0 (unallocated) run zero compute and contribute exactly zero.  For
    8-bit pools a grid step fetches a block of consecutive partitions'
    pages by async copies (the block follows from the page size, the
    stored width and the table width); the output does not depend on it.

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

    blocked = False
    ppb = 1
    if page_table is not None:
        Hkv, P, ps, _ = k_q.shape
        assert page_table.shape[0] == nb, (page_table.shape, nb)
        block_k = ps
        n_k_blocks = page_table.shape[1]
        BHkv = nb * Hkv
        pt = jnp.asarray(page_table, jnp.int32)
        # blocks of 8-bit pages that Mosaic can copy (whole lane rows wide;
        # not 4-bit pages of Dh/2 = 64 bytes) and that tile a lane row
        blocked = kv_bits == 8 and Dhk % _LANES == 0 and _LANES % ps == 0
        if blocked:
            ppb = min(max(1, _PAGED_BLOCK_TOKENS // ps), n_k_blocks)
            assert ppb * ps <= _LANES, (ppb, ps)
            # -1 entries up to whole blocks: skipped like unallocated pages
            pt = jnp.pad(pt, ((0, 0), (0, (-n_k_blocks) % ppb)),
                         constant_values=-1)
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
    n_parts = pt.shape[1]              # partitions computed, padding included
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
    grid = (BHkv, n_parts // ppb)
    table, frac = build_exp_table(lut_cfg)

    body = dict(
        block_k=block_k, r_pad=r_pad, g=G, causal=causal,
        window=window, sm_scale=1.0 / (Dh ** 0.5),
        score_scale=lut_cfg.score_scale, input_bits=lut_cfg.input_bits,
    )
    scalars = jnp.stack(
        [jnp.broadcast_to(q_off, (nb,)), jnp.broadcast_to(kvl, (nb,)),
         jnp.broadcast_to(ql, (nb,))]
    )                                                        # (3, nb)
    # (8, 128) block tiling: scales and the per-row partials m, den as
    # (1, n) lane rows, as in `pim_attention_pallas` (q scales cast to f32
    # there too)
    qsg = qsg.astype(jnp.float32)[:, None]
    in_specs = [
        pl.BlockSpec((1, r_pad, Dh), lambda b, k, s, t: (b, 0, 0)),
        pl.BlockSpec((1, 1, r_pad), lambda b, k, s, t: (b, 0, 0)),
    ]
    if blocked:
        # a page's scales as one 128-lane row, zeros past the page: Mosaic
        # copies no narrower row, and the (Hkv, P, 1, ps) plane takes the
        # same HBM under its (1, 128) tiling
        pad = ((0, 0), (0, 0), (0, _LANES - block_k))
        k_scale = jnp.pad(k_scale, pad)[..., None, :]
        v_scale = jnp.pad(v_scale, pad)[..., None, :]
        kernel = functools.partial(
            _decode_block_kernel, hkv_per_b=hkv_per_b, sq=Sq, ppb=ppb,
            n_blocks=grid[1], **body)
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 4 + [
            pl.BlockSpec((2, _LANES), lambda b, k, s, t: (0, 0))]
        page_buf = pltpu.VMEM((2, ppb, block_k, Dhk), k_q.dtype)
        scale_buf = pltpu.VMEM((2, ppb, 1, _LANES), jnp.float32)
        scratch = [page_buf, scale_buf, page_buf, scale_buf,
                   pltpu.SemaphoreType.DMA((2,))]
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        # the exp table's 256 entries as two lane rows
        operands = (k_q, k_scale, v_q, v_scale, table.reshape(2, _LANES))
    else:
        k_scale = k_scale[..., None, :]
        v_scale = v_scale[..., None, :]
        kernel = functools.partial(
            _decode_page_kernel, hkv_per_b=hkv_per_b, sq=Sq, kv_bits=kv_bits,
            **body)
        if page_table is not None:
            # the index map turns the logical KV partition into a physical
            # page: clamped to the trash page for unallocated entries (the
            # guarded kernel body never reads the placeholder block)
            def page_index(b, k, s, t, h=hkv_per_b):
                return (jax.lax.rem(b, h), jnp.maximum(t[b // h, k], 0), 0, 0)
            kv_spec = pl.BlockSpec((1, 1, block_k, Dhk), page_index)
            scale_spec = pl.BlockSpec((1, 1, 1, block_k), page_index)
        else:
            kv_spec = pl.BlockSpec((1, block_k, Dhk),
                                   lambda b, k, s, t: (b, k, 0))
            scale_spec = pl.BlockSpec((1, 1, block_k),
                                      lambda b, k, s, t: (b, 0, k))
        in_specs += [kv_spec, scale_spec, kv_spec, scale_spec,
                     pl.BlockSpec((256,), lambda b, k, s, t: (0,))]
        in_specs.append(pl.BlockSpec((16,), lambda b, k, s, t: (0,)))
        scratch = [pltpu.VMEM((r_pad, block_k), jnp.float32)]  # LUT exp
        if kv_bits == 4:   # the dequantized K, then V, page
            scratch.append(pltpu.VMEM((block_k, Dh), jnp.float32))
        params = None
        operands = (k_q, k_scale, v_q, v_scale, table,
                    jnp.asarray(KV4_LEVELS, jnp.float32))   # 4-bit codebook
    part_spec = pl.BlockSpec((1, ppb, 1, r_pad),
                             lambda b, k, s, t: (b, k, 0, 0))
    part_m, part_den, part_acc, iters = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                part_spec,
                part_spec,
                pl.BlockSpec((1, ppb, r_pad, Dh),
                             lambda b, k, s, t: (b, k, 0, 0)),
                pl.BlockSpec((1, ppb, 1, 1), lambda b, k, s, t: (b, k, 0, 0)),
            ],
            scratch_shapes=scratch,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, n_parts, 1, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((BHkv, n_parts, 1, r_pad), jnp.float32),
            jax.ShapeDtypeStruct((BHkv, n_parts, r_pad, Dh), jnp.float32),
            jax.ShapeDtypeStruct((BHkv, n_parts, 1, 1), jnp.int32),
        ],
        compiler_params=params,
        interpret=interpret,
    )(scalars, pt, qg, qsg, *operands)
    # the block walk's padding partitions are dropped: stage 2 combines
    # exactly the table's partitions
    part_m = part_m[:, :n_k_blocks, 0]
    part_den = part_den[:, :n_k_blocks, 0]
    part_acc = part_acc[:, :n_k_blocks]
    iters = iters[:, :n_k_blocks].reshape(BHkv, n_k_blocks)

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
