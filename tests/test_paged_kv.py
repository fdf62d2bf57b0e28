"""Paged KV cache coverage (ISSUE 3).

  * page-table routing of `paged_cache_write` (+ trash-page isolation)
  * bit-for-bit parity of paged vs dense-slot attention for RANDOM page-table
    permutations — behavioral gather reference and both Pallas kernels
  * page-boundary decode steps (kv_len at ps-1 / ps / ps+1 / 2ps)
  * zero compute on unallocated pages and empty slots (return_iters probe)
  * `cache_write_ragged` overflow: debug-mode raise + truncation contract
  * paged Scheduler: greedy parity vs dense scheduler and isolated
    generation, including a starved pool that forces stalls and eviction
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LUTSoftmaxConfig, PIMConfig
from repro.core import attention as attn
from repro.data import pipeline as data
from repro.kernels import ops
from repro.kernels.pim_attention import pim_attention_pallas
from repro.kernels.pim_decode import pim_decode_pallas
from repro.models.model_zoo import build_model
from repro.runtime import serve_lib

PIM = PIMConfig()
LUT = LUTSoftmaxConfig()


def _random_table(rng, lens, ps, n_tables, extra_pages=0):
    """Random permutation page table covering `lens` tokens per row; -1
    beyond each row's pages.  Page 0 (trash) is never assigned."""
    B = len(lens)
    P = B * n_tables + 1 + extra_pages
    perm = rng.permutation(np.arange(1, P))
    pt = np.full((B, n_tables), -1, np.int32)
    i = 0
    for b in range(B):
        for j in range(-(-int(lens[b]) // ps)):
            pt[b, j] = perm[i]
            i += 1
    return pt, P


def _paired_caches(key, B, max_len, lens, Hkv, Dh, ps, rng):
    """Same K/V written to a dense ragged cache and a paged pool with a
    random page table.  Returns (dense, pool, pt)."""
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, max_len, Hkv, Dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, max_len, Hkv, Dh)) * 0.5
    zeros = jnp.zeros(B, jnp.int32)
    lens_a = jnp.asarray(lens, jnp.int32)
    dense = attn.cache_write_ragged(
        attn.init_kv_cache(B, max_len, Hkv, Dh, ragged=True),
        k, v, zeros, PIM, seq_lens=lens_a)
    pt, P = _random_table(rng, lens, ps, max_len // ps)
    pool = attn.paged_cache_write(
        attn.init_paged_kv_cache(P, ps, Hkv, Dh),
        k, v, zeros, PIM, jnp.asarray(pt), seq_lens=lens_a)
    return dense, pool, jnp.asarray(pt)


# ---------------------------------------------------------------------------
# pool write semantics
# ---------------------------------------------------------------------------
def test_paged_cache_write_routing_and_trash_isolation():
    B, Hkv, Dh, ps = 2, 2, 8, 4
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (B, 6, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, 6, Hkv, Dh))
    pt = jnp.asarray([[3, 1], [2, -1]], jnp.int32)
    pool = attn.init_paged_kv_cache(5, ps, Hkv, Dh)
    # row 0: 6 valid tokens -> page 3 (tokens 0-3) + page 1 (tokens 4-5);
    # row 1: 3 valid tokens -> page 2; its tokens 4-5 hit the UNALLOCATED
    # second entry and must land in the trash page, not clobber anyone
    out = attn.paged_cache_write(pool, k, v, jnp.zeros(B, jnp.int32), PIM,
                                 pt, seq_lens=jnp.asarray([6, 3]))
    kq, _, ks, _ = attn.quantize_kv(k, v, PIM)
    np.testing.assert_array_equal(np.asarray(out.k_q[3]), np.asarray(kq[0, :4]))
    np.testing.assert_array_equal(np.asarray(out.k_q[1, :2]),
                                  np.asarray(kq[0, 4:6]))
    np.testing.assert_array_equal(np.asarray(out.k_q[2, :3]),
                                  np.asarray(kq[1, :3]))
    np.testing.assert_array_equal(np.asarray(out.k_scale[2, :3]),
                                  np.asarray(ks[1, :3]))
    # page 4 was never in any table: untouched
    np.testing.assert_array_equal(np.asarray(out.k_q[4]), 0)
    # row 1's token 3 (beyond seq_len, within its allocated page) is masked
    # garbage in page 2 — same contract as the dense cache; but tokens 4-5
    # (unallocated entry) went to trash, so page 1 row-0 data is intact
    np.testing.assert_array_equal(np.asarray(out.k_q[1, :2]),
                                  np.asarray(kq[0, 4:6]))


# ---------------------------------------------------------------------------
# parity: random page-table permutations, behavioral + both kernels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_parity_random_tables_bitexact(seed):
    """Decode + chunked-prefill attention over a randomly permuted page
    table is bit-identical to the dense slot cache, on the behavioral
    gather reference and both Pallas kernels."""
    B, max_len, H, Hkv, Dh, ps = 3, 64, 4, 2, 32, 16
    lens = np.array([[50, 17, 0], [64, 1, 33], [16, 15, 17]][seed], np.int32)
    rng = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    dense, pool, pt = _paired_caches(key, B, max_len, lens, Hkv, Dh, ps, rng)
    lens_a = jnp.asarray(lens)

    # behavioral: gathered pool view == dense cache, decode step
    q1 = jax.random.normal(key, (B, 1, H, Dh)) * 0.5
    offs1 = jnp.maximum(lens_a - 1, 0)
    gath = attn.paged_gather(pool, pt, lens_a)
    o_d = attn.pim_attention(q1, dense, PIM, LUT, offs1, out_dtype=jnp.float32)
    o_p = attn.pim_attention(q1, gath, PIM, LUT, offs1, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(o_d), np.asarray(o_p))

    # decode kernel (pages ARE the split-K partitions)
    qq = ops.kernel_attention_layout(q1, dense)
    ko_d = pim_decode_pallas(*qq, offs1, dense.length, block_k=ps,
                             interpret=True)
    q_q, qs = ops._q_kernel_layout(q1, PIM.input_bits)
    kq, ks, vq, vs = ops.paged_kernel_layout(pool)
    ko_p = pim_decode_pallas(q_q, qs, kq, ks, vq, vs, offs1, lens_a,
                             interpret=True, page_table=pt)
    np.testing.assert_array_equal(np.asarray(ko_d), np.asarray(ko_p))

    # prefill kernel (chunked ragged prefill of the last Sq tokens)
    Sq = 8
    q2 = jax.random.normal(jax.random.fold_in(key, 9), (B, Sq, H, Dh)) * 0.5
    offs2 = jnp.maximum(lens_a - Sq, 0)
    qq2 = ops.kernel_attention_layout(q2, dense)
    po_d = pim_attention_pallas(*qq2, offs2, dense.length, block_q=8,
                                block_k=ps, interpret=True)
    q_q2, qs2 = ops._q_kernel_layout(q2, PIM.input_bits)
    po_p = pim_attention_pallas(q_q2, qs2, kq, ks, vq, vs, offs2, lens_a,
                                block_q=8, interpret=True, page_table=pt)
    np.testing.assert_array_equal(np.asarray(po_d), np.asarray(po_p))


def test_paged_decode_zero_compute_on_unallocated_pages():
    """The iteration probe: slot b touches exactly Hkv * ceil(len_b / ps)
    partitions — unallocated table entries and empty slots run ZERO."""
    B, max_len, H, Hkv, Dh, ps = 4, 64, 4, 2, 32, 16
    lens = np.array([33, 16, 0, 1], np.int32)
    rng = np.random.RandomState(3)
    key = jax.random.PRNGKey(3)
    _, pool, pt = _paired_caches(key, B, max_len, lens, Hkv, Dh, ps, rng)
    q = jax.random.normal(key, (B, 1, H, Dh)) * 0.5
    q_q, qs = ops._q_kernel_layout(q, PIM.input_bits)
    kq, ks, vq, vs = ops.paged_kernel_layout(pool)
    lens_a = jnp.asarray(lens)
    o, iters = pim_decode_pallas(q_q, qs, kq, ks, vq, vs,
                                 jnp.maximum(lens_a - 1, 0), lens_a,
                                 interpret=True, return_iters=True,
                                 page_table=pt)
    per_slot = np.asarray(iters).reshape(B, Hkv, -1).sum(axis=(1, 2))
    np.testing.assert_array_equal(per_slot,
                                  [Hkv * -(-int(l) // ps) for l in lens])
    assert per_slot[2] == 0
    np.testing.assert_array_equal(np.asarray(o).reshape(B, H, Dh)[2], 0.0)
    # every unallocated (b, ki) table entry ran zero iterations
    it = np.asarray(iters).reshape(B, Hkv, -1)
    unalloc = np.asarray(pt) < 0
    assert (it[:, :, :][np.broadcast_to(unalloc[:, None], it.shape)] == 0).all()


def test_paged_decode_page_boundary_steps():
    """Decode exactly at page boundaries: kv_len of ps-1, ps, ps+1, 2*ps —
    bit-identical to dense, and the partition count steps up exactly when a
    new page starts being read."""
    ps, Hkv, H, Dh = 16, 2, 4, 32
    max_len = 4 * ps
    lens = np.array([ps - 1, ps, ps + 1, 2 * ps], np.int32)
    B = len(lens)
    rng = np.random.RandomState(5)
    key = jax.random.PRNGKey(5)
    dense, pool, pt = _paired_caches(key, B, max_len, lens, Hkv, Dh, ps, rng)
    q = jax.random.normal(key, (B, 1, H, Dh)) * 0.5
    lens_a = jnp.asarray(lens)
    offs = lens_a - 1
    qq = ops.kernel_attention_layout(q, dense)
    o_d = pim_decode_pallas(*qq, offs, dense.length, block_k=ps,
                            interpret=True)
    q_q, qs = ops._q_kernel_layout(q, PIM.input_bits)
    kq, ks, vq, vs = ops.paged_kernel_layout(pool)
    o_p, iters = pim_decode_pallas(q_q, qs, kq, ks, vq, vs, offs, lens_a,
                                   interpret=True, return_iters=True,
                                   page_table=pt)
    np.testing.assert_array_equal(np.asarray(o_d), np.asarray(o_p))
    per_slot = np.asarray(iters).reshape(B, Hkv, -1).sum(axis=(1, 2))
    np.testing.assert_array_equal(per_slot, [Hkv * 1, Hkv * 1, Hkv * 2,
                                             Hkv * 2])


# (lens, max_len, q_len, sq, head_dim, kv_bits, share): blocks of 2 pages of
# 16 below, so a 5-entry table ends in a ragged block
_BLOCK_WALK_CASES = {
    "ragged_table_width": ([80, 33, 1], 80, None, 1, 128, 8, False),
    "kv_len_block_edge_mid_zero": ([32, 40, 0, 64], 80, None, 1, 128, 8,
                                   False),
    "q_len_zero_rows": ([50, 70, 20], 80, [1, 0, 1], 1, 128, 8, False),
    "shared_page_ids": ([40, 24, 16], 64, None, 1, 128, 8, True),
    "verify_sq5": ([50, 33, 7, 64], 80, [5, 3, 5, 0], 5, 128, 8, False),
    "kv4_one_page_walk": ([50, 33, 0], 80, None, 1, 128, 4, False),
    "kv4_wide_heads": ([40, 17], 48, None, 1, 256, 4, False),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_WALK_CASES))
def test_paged_decode_block_walk_bitexact(case, monkeypatch):
    """The paged decode kernel's block walk (a grid step fetches a block
    of pages by async copies) is bit-equal to the dense kernel at
    block_k == page_size, with the same map of partitions run: ragged last
    blocks, kv_len at and inside block edges, empty rows, q_len 0 rows,
    scattered and shared page ids, verify rows.  Pools whose stored width
    is not whole lanes (4-bit pages of Dh/2 = 64 bytes) keep the one-page
    walk; 4-bit pages of 128 bytes take blocks too."""
    from repro.kernels import pim_decode as pd
    lens, max_len, q_len, sq, Dh, kv_bits, share = _BLOCK_WALK_CASES[case]
    B, H, Hkv, ps = len(lens), 4, 2, 16
    # 2 pages per block; traces made at another block size are dropped
    monkeypatch.setattr(pd, "_PAGED_BLOCK_TOKENS", 2 * ps)
    pim_decode_pallas.clear_cache()
    blocks = []
    real = pd._decode_block_kernel

    def spy(*args, **kw):
        blocks.append(kw["ppb"])
        return real(*args, **kw)

    monkeypatch.setattr(pd, "_decode_block_kernel", spy)
    seed = sum(map(ord, case))
    key = jax.random.PRNGKey(seed)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, max_len, Hkv, Dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, max_len, Hkv, Dh)) * 0.5
    if share:   # row 1 starts with row 0's first page, as a shared prefix
        k = k.at[1, :ps].set(k[0, :ps])
        v = v.at[1, :ps].set(v[0, :ps])
    lens_a = jnp.asarray(lens, jnp.int32)
    zeros = jnp.zeros(B, jnp.int32)
    dense = attn.cache_write_ragged(
        attn.init_kv_cache(B, max_len, Hkv, Dh, ragged=True, kv_bits=kv_bits),
        k, v, zeros, PIM, seq_lens=lens_a)
    pt, P = _random_table(np.random.RandomState(seed), lens, ps,
                          max_len // ps)
    pool = attn.paged_cache_write(
        attn.init_paged_kv_cache(P, ps, Hkv, Dh, kv_bits=kv_bits),
        k, v, zeros, PIM, jnp.asarray(pt), seq_lens=lens_a)
    if share:
        pt[1, 0] = pt[0, 0]
    ql = (jnp.asarray(q_len, jnp.int32) if q_len is not None
          else jnp.ones(B, jnp.int32))
    offs = jnp.maximum(lens_a - jnp.maximum(ql, 1), 0)
    q = jax.random.normal(jax.random.fold_in(key, 3), (B, sq, H, Dh)) * 0.5

    o_d, it_d = pim_decode_pallas(
        *ops.kernel_attention_layout(q, dense), offs, dense.length,
        block_k=ps, interpret=True, return_iters=True, q_len=ql)
    assert blocks == []                      # the dense walk is untouched
    q_q, qs = ops._q_kernel_layout(q, PIM.input_bits)
    o_p, it_p = pim_decode_pallas(
        q_q, qs, *ops.paged_kernel_layout(pool), offs, lens_a,
        interpret=True, return_iters=True, page_table=jnp.asarray(pt),
        q_len=ql)
    pim_decode_pallas.clear_cache()
    assert blocks == ([2] if kv_bits == 8 else [])
    np.testing.assert_array_equal(np.asarray(o_d), np.asarray(o_p))
    np.testing.assert_array_equal(np.asarray(it_d), np.asarray(it_p))
    assert np.asarray(it_p).sum() > 0 or not any(lens)


# ---------------------------------------------------------------------------
# cache_write_ragged overflow (satellite): debug check + truncation contract
# ---------------------------------------------------------------------------
def test_cache_write_ragged_overflow_debug_raises_eagerly():
    B, max_len, Hkv, Dh = 2, 8, 2, 4
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (B, 4, Hkv, Dh))
    v = jax.random.normal(jax.random.fold_in(key, 1), (B, 4, Hkv, Dh))
    cache = attn.init_kv_cache(B, max_len, Hkv, Dh, ragged=True)
    with pytest.raises(ValueError, match="overflow"):
        attn.cache_write_ragged(cache, k, v, jnp.asarray([0, 6]), PIM,
                                seq_lens=jnp.asarray([4, 4]), debug=True)
    # in-bounds writes never raise
    attn.cache_write_ragged(cache, k, v, jnp.asarray([0, 4]), PIM,
                            seq_lens=jnp.asarray([4, 4]), debug=True)


def test_cache_write_ragged_overflow_truncates_without_clobbering():
    """Overflowing tokens are DROPPED (not clamped onto max_len-1) and the
    row length is capped at max_len."""
    B, max_len, Hkv, Dh = 1, 8, 2, 4
    key = jax.random.PRNGKey(1)
    k0 = jax.random.normal(key, (B, max_len, Hkv, Dh))
    v0 = jax.random.normal(jax.random.fold_in(key, 1), (B, max_len, Hkv, Dh))
    cache = attn.init_kv_cache(B, max_len, Hkv, Dh, ragged=True)
    cache = attn.cache_write_ragged(cache, k0, v0, jnp.asarray([0]), PIM)
    last = np.asarray(cache.k_q[0, -1]).copy()
    # write 4 tokens at pos 6: tokens 2-3 overflow and must vanish
    k1 = jax.random.normal(jax.random.fold_in(key, 2), (B, 4, Hkv, Dh))
    v1 = jax.random.normal(jax.random.fold_in(key, 3), (B, 4, Hkv, Dh))
    out = attn.cache_write_ragged(cache, k1, v1, jnp.asarray([6]), PIM,
                                  seq_lens=jnp.asarray([4]))
    kq1, _, _, _ = attn.quantize_kv(k1, v1, PIM)
    np.testing.assert_array_equal(np.asarray(out.k_q[0, 6]),
                                  np.asarray(kq1[0, 0]))
    np.testing.assert_array_equal(np.asarray(out.k_q[0, 7]),
                                  np.asarray(kq1[0, 1]))
    assert int(out.length[0]) == max_len          # capped, not 10
    # and under jit the same write lowers fine (truncation, no OOB scatter)
    jit_write = jax.jit(lambda c, k, v: attn.cache_write_ragged(
        c, k, v, jnp.asarray([6]), PIM, seq_lens=jnp.asarray([4])))
    out2 = jit_write(cache, k1, v1)
    np.testing.assert_array_equal(np.asarray(out2.k_q), np.asarray(out.k_q))


# ---------------------------------------------------------------------------
# paged scheduler end-to-end
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("internlm2-1.8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_paged_scheduler_matches_dense_and_isolated(smoke_model):
    """Mixed-length requests through a paged pool (queueing + slot/page
    reuse) reproduce both the dense slot scheduler and isolated greedy."""
    cfg, model, params = smoke_model
    full = np.asarray(data.lm_batch(1, 4, 24, cfg.vocab_size))
    lens = [5, 17, 24, 9]
    budgets = [4, 7, 10, 13]
    dense = serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64)
    paged = serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                                page_size=16, num_pages=9)
    rd = [dense.submit(full[i][: lens[i]].tolist(), budgets[i])
          for i in range(4)]
    rp = [paged.submit(full[i][: lens[i]].tolist(), budgets[i])
          for i in range(4)]
    res_d, res_p = dense.run(), paged.run()
    for i in range(4):
        assert res_d[rd[i]] == res_p[rp[i]]
        p = {"tokens": jnp.asarray(full[i : i + 1, : lens[i]])}
        ref = np.asarray(serve_lib.greedy_generate(
            model, params, p, budgets[i], 64))[0]
        np.testing.assert_array_equal(np.asarray(res_p[rp[i]]), ref)
    assert len(paged.free_pages) == paged.num_pages - 1   # all pages freed


def test_paged_scheduler_starved_pool_stalls_and_evicts(smoke_model):
    """A pool with barely one sequence's worth of pages forces stalls and at
    least one eviction (continuation re-queue) — greedy output must still be
    exactly the isolated generation."""
    cfg, model, params = smoke_model
    full = np.asarray(data.lm_batch(4, 2, 30, cfg.vocab_size))
    sched = serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                                page_size=16, num_pages=6, decode_chunk=8)
    r0 = sched.submit(full[0].tolist(), 24)
    r1 = sched.submit(full[1].tolist(), 8)
    res = sched.run()
    for rid, b, budget in ((r0, 0, 24), (r1, 1, 8)):
        p = {"tokens": jnp.asarray(full[b : b + 1])}
        ref = np.asarray(serve_lib.greedy_generate(
            model, params, p, budget, 64))[0]
        np.testing.assert_array_equal(np.asarray(res[rid]), ref)
    assert sched.n_evictions >= 1
    assert len(sched.free_pages) == sched.num_pages - 1


def test_paged_generate_entrypoint_matches_classic(smoke_model):
    cfg, model, params = smoke_model
    prompt = {"tokens": jnp.asarray(data.lm_batch(0, 3, 8, cfg.vocab_size))}
    out_legacy = serve_lib.greedy_generate(model, params, prompt, 6, 32)
    out_paged = serve_lib.generate(model, params, prompt, 6, 32,
                                   continuous_batching=True,
                                   page_size=8)
    np.testing.assert_array_equal(np.asarray(out_legacy),
                                  np.asarray(out_paged))


def test_paged_scheduler_rejects_undersized_pool(smoke_model):
    cfg, model, params = smoke_model
    with pytest.raises(ValueError, match="full-length"):
        serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                            page_size=16, num_pages=3)
