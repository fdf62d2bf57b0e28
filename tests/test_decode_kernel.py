"""Flash-decode kernel + grid-pruning validation (interpret mode).

  * decode-vs-prefill-kernel and decode-vs-fp parity of `pim_decode_pallas`
  * kv_len early-exit: decode touches only ceil(kv_len/block_k) blocks,
    independent of the padded cache max_len
  * causal / window block pruning is bit-equivalent to the dense grid and
    executes the analytically expected number of block iterations
  * the online accumulator: rescales against the two-pass oracle, fully
    masked blocks as exact no-ops, stale pool pages as exact zeros
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import PIMConfig
from repro.core import attention as attn
from repro.core.attention import expected_kv_block_iters
from repro.kernels import ops, ref
from repro.kernels.pim_attention import pim_attention_pallas
from repro.kernels.pim_decode import paged_block_pages, pim_decode_pallas


def _setup(key, B, Sq, max_len, kv_len, H, Hkv, Dh, scale=0.5):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, Sq, H, Dh)) * scale
    k = jax.random.normal(k2, (B, kv_len, Hkv, Dh)) * scale
    v = jax.random.normal(k3, (B, kv_len, Hkv, Dh)) * scale
    cache = attn.cache_write(attn.init_kv_cache(B, max_len, Hkv, Dh), k, v, 0,
                             PIMConfig())
    return q, k, v, cache


def _layout(q, cache):
    return ops.kernel_attention_layout(q, cache)


# ---------------------------------------------------------------------------
# decode parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dims", [
    (1, 96, 96, 4, 1, 64),     # MQA, full cache
    (2, 128, 100, 4, 2, 32),   # GQA, partially-filled cache
    (1, 256, 96, 8, 8, 64),    # MHA (q_per_kv == 1)
])
def test_decode_matches_prefill_kernel(dims):
    B, max_len, kv_len, H, Hkv, Dh = dims
    q, _, _, cache = _setup(jax.random.PRNGKey(sum(dims)), B, 1, max_len,
                            kv_len, H, Hkv, Dh)
    qq = _layout(q, cache)
    off = jnp.int32(kv_len - 1)
    o_d = pim_decode_pallas(*qq, off, cache.length, block_k=64, interpret=True)
    o_p = pim_attention_pallas(*qq, off, cache.length, block_k=64,
                               interpret=True)
    rel = jnp.linalg.norm(o_d - o_p) / (jnp.linalg.norm(o_p) + 1e-9)
    assert float(rel) < 5e-3


def test_decode_matches_ref_and_fp():
    B, max_len, kv_len, H, Hkv, Dh = 2, 128, 90, 4, 2, 64
    q, k, v, cache = _setup(jax.random.PRNGKey(0), B, 1, max_len, kv_len, H,
                            Hkv, Dh)
    qq = _layout(q, cache)
    off = jnp.int32(kv_len - 1)
    o_d = pim_decode_pallas(*qq, off, cache.length, block_k=64, interpret=True)
    o_r = ref.pim_attention_ref(*qq, off, kv_len)
    rel = jnp.linalg.norm(o_d - o_r) / (jnp.linalg.norm(o_r) + 1e-9)
    assert float(rel) < 5e-3
    o_bhqd = o_d.reshape(B, H, 1, Dh).transpose(0, 2, 1, 3)
    o_fp = attn.fp_attention(q, k, v, q_offset=off).astype(jnp.float32)
    rel_fp = jnp.linalg.norm(o_bhqd - o_fp) / jnp.linalg.norm(o_fp)
    assert float(rel_fp) < 0.06


def test_ops_dispatch_decode_vs_prefill_kernel_agree():
    """ops.pim_flash_attention must route Sq==1 to the decode kernel and
    stay numerically consistent with the forced prefill-kernel path."""
    B, max_len, kv_len, H, Hkv, Dh = 1, 96, 96, 4, 2, 32
    q, _, _, cache = _setup(jax.random.PRNGKey(5), B, 1, max_len, kv_len, H,
                            Hkv, Dh)
    o_dec = ops.pim_flash_attention(q, cache, kv_len - 1,
                                    out_dtype=jnp.float32)
    o_pre = ops.pim_flash_attention(q, cache, kv_len - 1,
                                    out_dtype=jnp.float32,
                                    decode_kernel=False)
    rel = jnp.linalg.norm(o_dec - o_pre) / (jnp.linalg.norm(o_pre) + 1e-9)
    assert float(rel) < 5e-3


# ---------------------------------------------------------------------------
# kv_len early exit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_len", [1, 63, 64, 130])
def test_decode_kv_len_early_exit(kv_len):
    """Decode touches ceil(kv_len/block_k) partitions — not max_len/block_k."""
    B, max_len, H, Hkv, Dh = 1, 512, 4, 2, 32
    q, _, _, cache = _setup(jax.random.PRNGKey(kv_len), B, 1, max_len, kv_len,
                            H, Hkv, Dh)
    qq = _layout(q, cache)
    off = jnp.int32(kv_len - 1)
    _, iters = pim_decode_pallas(*qq, off, cache.length, block_k=64,
                                 interpret=True, return_iters=True)
    per_head = np.asarray(iters.sum(axis=1))
    assert iters.shape[1] == max_len // 64          # grid spans padded cache
    np.testing.assert_array_equal(per_head, -(-kv_len // 64))


def test_decode_iters_independent_of_max_len():
    kv_len, B, H, Hkv, Dh = 70, 1, 2, 1, 32
    counts = []
    for max_len in (128, 512):
        q, _, _, cache = _setup(jax.random.PRNGKey(7), B, 1, max_len, kv_len,
                                H, Hkv, Dh)
        qq = _layout(q, cache)
        _, iters = pim_decode_pallas(*qq, jnp.int32(kv_len - 1), cache.length,
                                     block_k=64, interpret=True,
                                     return_iters=True)
        counts.append(int(iters.sum()))
    assert counts[0] == counts[1] == Hkv * -(-kv_len // 64)


def test_prefill_kernel_kv_len_early_exit():
    """The pruned prefill kernel also skips blocks beyond cache.length."""
    B, max_len, kv_len, Sq, H, Hkv, Dh = 1, 256, 40, 4, 2, 2, 32
    q, k, v, cache = _setup(jax.random.PRNGKey(9), B, Sq, max_len, kv_len, H,
                            Hkv, Dh)
    qq = _layout(q, cache)
    off = jnp.int32(kv_len - Sq)
    o, iters = pim_attention_pallas(*qq, off, cache.length, block_q=8,
                                    block_k=32, interpret=True,
                                    return_iters=True)
    exp = expected_kv_block_iters(Sq, max_len, kv_len - Sq, 8, 32,
                                  causal=True, kv_valid_len=kv_len)
    assert int(iters.sum()) == B * H * exp
    assert int(iters.sum()) < B * H * (Sq // 8 + 1) * (max_len // 32) / 2
    o_fp = attn.fp_attention(q, k, v, q_offset=off).astype(jnp.float32)
    o = o.reshape(B, H, Sq, Dh).transpose(0, 2, 1, 3)
    rel = jnp.linalg.norm(o - o_fp) / jnp.linalg.norm(o_fp)
    assert float(rel) < 0.06


# ---------------------------------------------------------------------------
# causal / window pruning equivalence
# ---------------------------------------------------------------------------
def test_causal_pruning_bit_equal_and_halves_iters():
    B, S, H, Hkv, Dh, bq, bk = 1, 128, 2, 1, 32, 16, 16
    q, _, _, cache = _setup(jax.random.PRNGKey(1), B, S, S, S, H, Hkv, Dh)
    qq = _layout(q, cache)
    o_p, it_p = pim_attention_pallas(*qq, jnp.int32(0), cache.length,
                                     block_q=bq, block_k=bk, interpret=True,
                                     prune=True, return_iters=True)
    o_d, it_d = pim_attention_pallas(*qq, jnp.int32(0), cache.length,
                                     block_q=bq, block_k=bk, interpret=True,
                                     prune=False, return_iters=True)
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_d))
    n = S // bq
    assert int(it_d.sum()) == B * H * n * n
    assert int(it_p.sum()) == B * H * n * (n + 1) // 2      # lower triangle
    assert int(it_p.sum()) == B * H * expected_kv_block_iters(S, S, 0, bq, bk)


def test_window_pruning_bit_equal_and_correct():
    B, S, H, Hkv, Dh, W = 1, 128, 2, 2, 32, 24
    q, k, v, cache = _setup(jax.random.PRNGKey(2), B, S, S, S, H, Hkv, Dh)
    qq = _layout(q, cache)
    o_p, it_p = pim_attention_pallas(*qq, jnp.int32(0), cache.length,
                                     window=W, block_q=16, block_k=16,
                                     interpret=True, prune=True,
                                     return_iters=True)
    o_d, it_d = pim_attention_pallas(*qq, jnp.int32(0), cache.length,
                                     window=W, block_q=16, block_k=16,
                                     interpret=True, prune=False,
                                     return_iters=True)
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_d))
    exp = expected_kv_block_iters(S, S, 0, 16, 16, causal=True, window=W)
    assert int(it_p.sum()) == B * H * exp < int(it_d.sum())
    o_fp = attn.fp_attention(q, k, v, 0, window=W).astype(jnp.float32)
    o = o_p.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)
    rel = jnp.linalg.norm(o - o_fp) / jnp.linalg.norm(o_fp)
    assert float(rel) < 0.06


# ---------------------------------------------------------------------------
# speculative verify rows (multi-query decode launches)
# ---------------------------------------------------------------------------
def test_verify_rows_bit_identical_to_single_steps():
    """A q_len = k+1 verify launch through the decode kernel must produce,
    per position, EXACTLY the bits of the k+1 individual Sq == 1 decode
    steps it replaces — the kernel half of greedy speculative streams
    being bit-identical to non-speculative ones."""
    B, max_len, kv_len, Sq, H, Hkv, Dh = 2, 128, 90, 3, 4, 2, 32
    q, _, _, cache = _setup(jax.random.PRNGKey(11), B, Sq, max_len, kv_len,
                            H, Hkv, Dh)
    off = kv_len - Sq
    o_multi = ops.pim_flash_attention(q, cache, off, out_dtype=jnp.float32,
                                      force_decode_kernel=True)
    for l in range(Sq):
        o_one = ops.pim_flash_attention(q[:, l: l + 1], cache, off + l,
                                        out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(o_multi[:, l]),
                                      np.asarray(o_one[:, 0]))


def test_verify_rows_ragged_q_len():
    """Per-row ragged verify lengths: row b's first q_len[b] positions are
    bit-identical to its single-step launches (positions past q_len[b] are
    padding the caller slices away)."""
    B, max_len, kv_len, Sq, H, Hkv, Dh = 2, 128, 80, 4, 4, 2, 32
    q, _, _, cache = _setup(jax.random.PRNGKey(12), B, Sq, max_len, kv_len,
                            H, Hkv, Dh)
    ql = jnp.asarray([2, 4], jnp.int32)
    off = jnp.asarray([kv_len - 2, kv_len - 4], jnp.int32)
    o_multi = ops.pim_flash_attention(q, cache, off, out_dtype=jnp.float32,
                                      force_decode_kernel=True, q_len=ql)
    for b in range(B):
        for l in range(int(ql[b])):
            o_one = ops.pim_flash_attention(
                q[b: b + 1, l: l + 1], _slice_cache(cache, b),
                off[b: b + 1] + l, out_dtype=jnp.float32)
            np.testing.assert_array_equal(np.asarray(o_multi[b, l]),
                                          np.asarray(o_one[0, 0]))


def _slice_cache(cache, b):
    length = jnp.broadcast_to(jnp.reshape(cache.length, (-1,)),
                              (cache.k_q.shape[0],))
    return cache._replace(k_q=cache.k_q[b: b + 1], v_q=cache.v_q[b: b + 1],
                          k_scale=cache.k_scale[b: b + 1],
                          v_scale=cache.v_scale[b: b + 1],
                          length=length[b: b + 1])


def test_verify_row_iter_probe_matches_analytic():
    """Multi-query verify launches run exactly the analytic mirror's count
    with block_q == Sq (one sublane-packed q block per slot; per-partition
    reach is the union over valid rows)."""
    B, max_len, kv_len, Sq, H, Hkv, Dh, bk = 1, 256, 100, 4, 2, 1, 32, 32
    q, _, _, cache = _setup(jax.random.PRNGKey(13), B, Sq, max_len, kv_len,
                            H, Hkv, Dh)
    qq = _layout(q, cache)
    for ql in (1, 2, 4):
        off = jnp.int32(kv_len - ql)
        _, iters = pim_decode_pallas(*qq, off, cache.length, block_k=bk,
                                     interpret=True, return_iters=True,
                                     q_len=jnp.full((B,), ql, jnp.int32))
        exp = expected_kv_block_iters(Sq, max_len, kv_len - ql, Sq, bk,
                                      causal=True, kv_valid_len=kv_len,
                                      q_valid_len=ql)
        np.testing.assert_array_equal(np.asarray(iters.sum(axis=1)), exp)
    # q_len == 0 rows cost zero partitions
    _, iters0 = pim_decode_pallas(*qq, jnp.int32(0), cache.length,
                                  block_k=bk, interpret=True,
                                  return_iters=True,
                                  q_len=jnp.zeros((B,), jnp.int32))
    assert int(iters0.sum()) == 0


def test_verify_single_row_bit_identical_to_plain_decode():
    """Sq > 1 padding must not perturb the Sq == 1 fast path: a verify
    launch with q_len == 1 equals the plain decode launch bit-for-bit."""
    B, max_len, kv_len, H, Hkv, Dh = 2, 128, 77, 4, 2, 32
    q, _, _, cache = _setup(jax.random.PRNGKey(14), B, 3, max_len, kv_len,
                            H, Hkv, Dh)
    off = jnp.int32(kv_len - 1)
    o_multi = ops.pim_flash_attention(q, cache, off, out_dtype=jnp.float32,
                                      force_decode_kernel=True,
                                      q_len=jnp.ones((B,), jnp.int32))
    o_one = ops.pim_flash_attention(q[:, :1], cache, off,
                                    out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(o_multi[:, :1]),
                                  np.asarray(o_one))


def test_decode_window_parity():
    B, max_len, kv_len, H, Hkv, Dh, W = 1, 256, 150, 2, 1, 32, 40
    q, k, v, cache = _setup(jax.random.PRNGKey(3), B, 1, max_len, kv_len, H,
                            Hkv, Dh)
    qq = _layout(q, cache)
    off = jnp.int32(kv_len - 1)
    o_d, iters = pim_decode_pallas(*qq, off, cache.length, window=W,
                                   block_k=32, interpret=True,
                                   return_iters=True)
    o_p = pim_attention_pallas(*qq, off, cache.length, window=W, block_k=32,
                               interpret=True)
    rel = jnp.linalg.norm(o_d - o_p) / (jnp.linalg.norm(o_p) + 1e-9)
    assert float(rel) < 5e-3
    exp = expected_kv_block_iters(1, max_len, kv_len - 1, 1, 32,
                                  causal=True, window=W, kv_valid_len=kv_len)
    np.testing.assert_array_equal(np.asarray(iters.sum(axis=1)), exp)


# ---------------------------------------------------------------------------
# the online accumulator: one running (max, denominator, acc) per grid row
# ---------------------------------------------------------------------------
def _paged(k, v, lens, ps, rng, extra_pages=0, kv_bits=8):
    """K/V (B, S, Hkv, Dh) written to a page pool through a random page
    table (-1 past each row's pages; page 0, the trash page, never
    assigned): (pool, table)."""
    B, S, Hkv, Dh = k.shape
    n = S // ps
    P = B * n + 1 + extra_pages
    perm = rng.permutation(np.arange(1, P))
    pt = np.full((B, n), -1, np.int32)
    i = 0
    for b in range(B):
        for j in range(-(-int(lens[b]) // ps)):
            pt[b, j] = perm[i]
            i += 1
    pool = attn.paged_cache_write(
        attn.init_paged_kv_cache(P, ps, Hkv, Dh, kv_bits=kv_bits), k, v,
        jnp.zeros(B, jnp.int32), PIMConfig(), jnp.asarray(pt),
        seq_lens=jnp.asarray(lens, jnp.int32))
    return pool, jnp.asarray(pt)


@pytest.mark.parametrize("walk", ["dense", "paged"])
def test_decode_rising_max_matches_ref(walk):
    """A row of 24 KV blocks whose scores climb along the cache: each
    block raises the running max, so every step rescales the running sums
    by the LUT.  The result stays within the two-pass oracle's bound, on
    the dense walk and on the paged block walk (blocks of 8 pages)."""
    B, H, Hkv, Dh, ps, n_blocks = 1, 2, 1, 128, 16, 24
    S = n_blocks * 128
    key = jax.random.PRNGKey(24)
    u = jax.random.normal(key, (Dh,))
    q = jnp.broadcast_to(u, (B, 1, H, Dh))
    ramp = 6.0 * jnp.arange(S) / S                 # score climbs 0 -> 6
    k = (0.5 * jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh))
         + ramp[None, :, None, None] * (u / jnp.linalg.norm(u)))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh)) * 0.5
    cache = attn.cache_write(attn.init_kv_cache(B, S, Hkv, Dh), k, v, 0,
                             PIMConfig())
    qq = _layout(q, cache)
    off = jnp.int32(S - 1)
    # the score codes' block maxima climb: the max moves late, many times
    s = (qq[0][0].astype(jnp.float32) @ qq[2][0].astype(jnp.float32).T
         * qq[1][0][:, None] * qq[3][0][None] / Dh ** 0.5)
    block_max = np.asarray(jnp.round(s * 16).reshape(n_blocks, -1).max(-1))
    assert np.argmax(block_max) >= n_blocks - 3
    assert block_max[-1] - block_max[0] > 40
    assert (np.diff(block_max) > 0).sum() >= n_blocks // 2
    if walk == "dense":
        o = pim_decode_pallas(*qq, off, cache.length, block_k=128,
                              interpret=True)
    else:
        pool, pt = _paged(k, v, [S], ps, np.random.RandomState(0))
        kq, ks, vq, vs = ops.paged_kernel_layout(pool)
        assert paged_block_pages(ps, Dh, Dh, pt.shape[1]) == 8
        q_q, qs = ops._q_kernel_layout(q, PIMConfig().input_bits)
        o = pim_decode_pallas(q_q, qs, kq, ks, vq, vs, off,
                              jnp.asarray([S], jnp.int32), interpret=True,
                              page_table=pt)
    o_r = ref.pim_attention_ref(*qq, off, S)
    rel = jnp.linalg.norm(o - o_r) / (jnp.linalg.norm(o_r) + 1e-9)
    assert float(rel) < 5e-3


@pytest.mark.parametrize("walk", ["dense", "paged"])
def test_verify_masked_block_is_exact_noop(walk):
    """Verify rows at positions p, p+1, p+2 where p ends a KV block: the
    next block is fully masked for row 0 but needed for rows 1 and 2.
    Folding it in leaves row 0's running sums bit-unchanged, so row 0
    equals its single step (kv_len p+1, which never runs that block)."""
    B, H, Hkv, Sq = 2, 4, 2, 3
    Dh, bk = (32, 16) if walk == "dense" else (128, 128)
    p = 2 * bk - 1                       # the last token of block 1
    S = 4 * bk
    key = jax.random.PRNGKey(31)
    q, k, v, cache = _setup(key, B, Sq, S, p + Sq, H, Hkv, Dh)
    lens = jnp.full((B,), p + Sq, jnp.int32)
    if walk == "dense":
        qq = _layout(q, cache)
        kv = qq[2:]
        multi = dict(block_k=bk)
    else:
        pool, pt = _paged(jnp.pad(k, ((0, 0), (0, S - p - Sq), (0, 0),
                                      (0, 0))),
                          jnp.pad(v, ((0, 0), (0, S - p - Sq), (0, 0),
                                      (0, 0))),
                          [p + Sq] * B, 16, np.random.RandomState(1))
        kv = ops.paged_kernel_layout(pool)
        multi = dict(page_table=pt)
    q_q, qs = ops._q_kernel_layout(q, PIMConfig().input_bits)
    o_multi, it = pim_decode_pallas(
        q_q, qs, *kv, jnp.full((B,), p, jnp.int32), lens,
        q_len=jnp.full((B,), Sq, jnp.int32), interpret=True,
        return_iters=True, **multi)
    o_one, it1 = pim_decode_pallas(
        q_q[:, :1], qs[:, :1], *kv, jnp.full((B,), p, jnp.int32),
        jnp.full((B,), p + 1, jnp.int32), interpret=True, return_iters=True,
        **multi)
    # the verify launch ran a block (or page) the single step did not
    assert int(np.asarray(it).sum()) > int(np.asarray(it1).sum())
    np.testing.assert_array_equal(np.asarray(o_multi[:, 0]),
                                  np.asarray(o_one[:, 0]))


@pytest.mark.parametrize("walk,Dh,kv_bits", [
    ("block", 128, 8), ("one_page", 32, 8), ("kv4_one_page", 128, 4)])
def test_stale_pool_pages_add_exact_zeros(walk, Dh, kv_bits):
    """Pages and scale rows the table never points at (the trash page,
    pages of no slot) hold garbage bytes and NaN scales.  They reach no
    output: every output is finite and bit-equal to the launch over the
    same pool with those pages zeroed."""
    B, H, Hkv, ps, S = 3, 4, 2, 16, 256
    lens = [200, 37, 0]
    key = jax.random.PRNGKey(47)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, Dh)) * 0.5
    pool, pt = _paged(k, v, lens, ps, np.random.RandomState(2),
                      extra_pages=6, kv_bits=kv_bits)
    used = set(np.asarray(pt)[np.asarray(pt) >= 0].tolist())
    stale = np.array([i for i in range(pool.k_q.shape[0]) if i not in used])
    assert 0 in stale and len(stale) > 6

    def fill(byte, scale):
        return pool._replace(
            k_q=pool.k_q.at[stale].set(byte), v_q=pool.v_q.at[stale].set(byte),
            k_scale=pool.k_scale.at[stale].set(scale),
            v_scale=pool.v_scale.at[stale].set(scale))

    kq = ops.paged_kernel_layout(pool)[0]
    assert (paged_block_pages(ps, kq.shape[-1], Dh, pt.shape[1]) > 1) == (
        walk == "block")
    q = jax.random.normal(jax.random.fold_in(key, 3), (B, 1, H, Dh)) * 0.5
    q_q, qs = ops._q_kernel_layout(q, PIMConfig().input_bits)
    lens_a = jnp.asarray(lens, jnp.int32)
    offs = jnp.maximum(lens_a - 1, 0)
    outs = [pim_decode_pallas(q_q, qs, *ops.paged_kernel_layout(
                fill(byte, scale)), offs, lens_a, interpret=True,
                page_table=pt)
            for byte, scale in ((-77, jnp.nan), (0, 0.0))]
    assert np.isfinite(np.asarray(outs[0])).all()
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
