"""4-bit blockwise KV-cache storage (`kv_bits=4`) coverage (ISSUE 9).

  * dynamic-map codec properties: level-table shape, pack/unpack inverse,
    encode determinism, roundtrip error bounded by the per-block absmax
    step, all-zero and single-token blocks (hypothesis-driven when
    available, plus deterministic seeds always)
  * 4-bit kernel exactness: both Pallas kernels over packed codes are
    bit-identical to the same kernels over an int8 cache holding the
    dequantized level values (the f32 LUT-dequant dot is exact)
  * bit-for-bit parity of 4-bit paged vs dense-slot attention for RANDOM
    page-table permutations — behavioral gather reference and both Pallas
    kernels (mirrors `test_paged_kv.py` at kv_bits=8)
  * scheduler: kv_bits=8 override is bit-identical to the default; 4-bit
    paged == 4-bit dense results; page/spill byte accounting halves
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LUTSoftmaxConfig, PIMConfig
from repro.core import attention as attn
from repro.core import quant
from repro.kernels import ops
from repro.kernels.pim_attention import pim_attention_pallas
from repro.kernels.pim_decode import pim_decode_pallas
from repro.models.model_zoo import build_model
from repro.runtime import serve_lib

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

PIM = PIMConfig()
LUT = LUTSoftmaxConfig()

# the widest gap between adjacent dynamic-map levels (int8-snapped) bounds
# the roundtrip error: |x - dec| <= gap/2 * scale, scale = absmax/127
_MAX_GAP = int(np.max(np.diff(quant.KV4_LEVELS.astype(np.int32))))


# ---------------------------------------------------------------------------
# codec properties
# ---------------------------------------------------------------------------
def test_kv4_level_table():
    lv = quant.KV4_LEVELS
    assert lv.shape == (16,) and lv.dtype == np.int8
    assert np.unique(lv).size == 16
    assert (np.sort(lv) == lv).all()          # sorted -> searchsorted encode
    assert 0 in lv and 127 in lv              # exact zero + full-scale codes
    # signed map: every negative magnitude has a positive partner (the +1.0
    # entry is the one asymmetric extra of the odd 16-level budget)
    neg = set(-int(x) for x in lv[lv < 0])
    assert neg <= set(int(x) for x in lv[lv > 0])


def _roundtrip_err(x):
    """Max |x - dec| / scale over the last axis' absmax blocks."""
    scale = quant.symmetric_max_scale(x, PIM.input_bits, axis=-1)
    packed = quant.kv4_encode(x, scale)
    dec = quant.kv4_decode_int8(packed).astype(jnp.float32) * scale
    return float(jnp.max(jnp.abs(x - dec) / scale))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_kv4_roundtrip_error_bound(seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 7, 3, 32)) * 10.0**seed
    assert _roundtrip_err(x) <= _MAX_GAP / 2 + 1e-3


def test_kv4_roundtrip_edge_blocks():
    # all-zero block: eps-clamped scale, codes decode to exactly 0
    z = jnp.zeros((2, 3, 8))
    scale = quant.symmetric_max_scale(z, PIM.input_bits, axis=-1)
    dec = quant.kv4_decode_int8(quant.kv4_encode(z, scale))
    np.testing.assert_array_equal(np.asarray(dec), 0)
    # single-token block (leading dims of size 1) and the smallest packable
    # width (2 -> 1 byte)
    one = jnp.asarray([[[0.75, -0.3]]])
    s1 = quant.symmetric_max_scale(one, PIM.input_bits, axis=-1)
    p1 = quant.kv4_encode(one, s1)
    assert p1.shape == (1, 1, 1)
    d1 = quant.kv4_decode_int8(p1).astype(jnp.float32) * s1
    assert float(jnp.max(jnp.abs(one - d1) / s1)) <= _MAX_GAP / 2 + 1e-3
    # a positive block absmax maps to the full-scale +127 level exactly
    # (the signed map's one asymmetric entry: -127 has no partner level)
    assert float(d1.max()) == pytest.approx(0.75, rel=1e-6)


def test_kv4_encode_deterministic_and_pack_inverse():
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, 2, 16))
    scale = quant.symmetric_max_scale(x, PIM.input_bits, axis=-1)
    a = np.asarray(quant.kv4_encode(x, scale))
    b = np.asarray(quant.kv4_encode(x, scale))
    np.testing.assert_array_equal(a, b)
    # pack/unpack is an exact inverse on every possible code pair
    codes = jnp.stack(jnp.meshgrid(jnp.arange(16), jnp.arange(16)),
                      -1).reshape(-1, 2)
    np.testing.assert_array_equal(
        np.asarray(quant.unpack_codes4(quant.pack_codes4(codes))),
        np.asarray(codes))


if HAVE_HYPOTHESIS:
    _settings = dict(max_examples=25, deadline=None)

    @given(st.integers(1, 8), st.integers(1, 32),
           st.floats(1e-3, 1e3), st.integers(0, 2**31 - 1))
    @settings(**_settings)
    def test_kv4_roundtrip_bound_hypothesis(rows, half_dim, mag, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed),
                              (rows, 2 * half_dim)) * mag
        assert _roundtrip_err(x) <= _MAX_GAP / 2 + 1e-3

    @given(st.integers(1, 64), st.integers(0, 2**31 - 1))
    @settings(**_settings)
    def test_kv4_pack_inverse_hypothesis(n, seed):
        codes = jax.random.randint(jax.random.PRNGKey(seed), (n, 6), 0, 16)
        np.testing.assert_array_equal(
            np.asarray(quant.unpack_codes4(quant.pack_codes4(codes))),
            np.asarray(codes))


# ---------------------------------------------------------------------------
# kernel exactness: packed codes == dequantized int8 levels, bit for bit
# ---------------------------------------------------------------------------
def _kv4_caches(key, B, max_len, lens, Hkv, Dh):
    """Same K/V in a 4-bit ragged cache and an int8 cache holding the
    DEQUANTIZED level values (same scale planes)."""
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, max_len, Hkv, Dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, max_len, Hkv, Dh)) * 0.5
    zeros = jnp.zeros(B, jnp.int32)
    lens_a = jnp.asarray(lens, jnp.int32)
    c4 = attn.cache_write_ragged(
        attn.init_kv_cache(B, max_len, Hkv, Dh, ragged=True, kv_bits=4),
        k, v, zeros, PIM, seq_lens=lens_a)
    c8 = c4._replace(k_q=quant.kv4_decode_int8(c4.k_q),
                     v_q=quant.kv4_decode_int8(c4.v_q))
    return c4, c8


def test_kv4_kernels_match_dequantized_int8_bitexact():
    """The fused LUT-dequant is exact: both kernels over the packed cache
    equal the same kernels over int8 level values (f32 dots of exact
    integers stay below 2**24)."""
    B, max_len, H, Hkv, Dh = 3, 64, 4, 2, 32
    lens = jnp.asarray([64, 17, 1], jnp.int32)
    key = jax.random.PRNGKey(0)
    c4, c8 = _kv4_caches(key, B, max_len, lens, Hkv, Dh)

    q1 = jax.random.normal(key, (B, 1, H, Dh)) * 0.5
    offs1 = jnp.maximum(lens - 1, 0)
    o4 = pim_decode_pallas(*ops.kernel_attention_layout(q1, c4), offs1,
                           c4.length, block_k=16, interpret=True)
    o8 = pim_decode_pallas(*ops.kernel_attention_layout(q1, c8), offs1,
                           c8.length, block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(o4), np.asarray(o8))

    q2 = jax.random.normal(jax.random.fold_in(key, 9), (B, 8, H, Dh)) * 0.5
    offs2 = jnp.maximum(lens - 8, 0)
    p4 = pim_attention_pallas(*ops.kernel_attention_layout(q2, c4), offs2,
                              c4.length, block_q=8, block_k=16,
                              interpret=True)
    p8 = pim_attention_pallas(*ops.kernel_attention_layout(q2, c8), offs2,
                              c8.length, block_q=8, block_k=16,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(p4), np.asarray(p8))

    # behavioral path unpacks to the same int8 levels
    b4 = attn.pim_attention(q1, c4, PIM, LUT, offs1, out_dtype=jnp.float32)
    b8 = attn.pim_attention(q1, c8, PIM, LUT, offs1, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(b4), np.asarray(b8))


# ---------------------------------------------------------------------------
# parity: 4-bit paged vs dense, random tables, behavioral + both kernels
# ---------------------------------------------------------------------------
def _random_table(rng, lens, ps, n_tables):
    """Random permutation page table covering `lens` tokens per row; -1
    beyond each row's pages.  Page 0 (trash) is never assigned."""
    B = len(lens)
    P = B * n_tables + 1
    perm = rng.permutation(np.arange(1, P))
    pt = np.full((B, n_tables), -1, np.int32)
    i = 0
    for b in range(B):
        for j in range(-(-int(lens[b]) // ps)):
            pt[b, j] = perm[i]
            i += 1
    return pt, P


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kv4_paged_parity_random_tables_bitexact(seed):
    """`test_paged_parity_random_tables_bitexact` at kv_bits=4: packed-code
    pages behave exactly like the packed dense cache on all three paths."""
    B, max_len, H, Hkv, Dh, ps = 3, 64, 4, 2, 32, 16
    lens = np.array([[50, 17, 0], [64, 1, 33], [16, 15, 17]][seed], np.int32)
    rng = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, max_len, Hkv, Dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, max_len, Hkv, Dh)) * 0.5
    zeros = jnp.zeros(B, jnp.int32)
    lens_a = jnp.asarray(lens, jnp.int32)
    dense = attn.cache_write_ragged(
        attn.init_kv_cache(B, max_len, Hkv, Dh, ragged=True, kv_bits=4),
        k, v, zeros, PIM, seq_lens=lens_a)
    pt, P = _random_table(rng, lens, ps, max_len // ps)
    pool = attn.paged_cache_write(
        attn.init_paged_kv_cache(P, ps, Hkv, Dh, kv_bits=4),
        k, v, zeros, PIM, jnp.asarray(pt), seq_lens=lens_a)
    pt = jnp.asarray(pt)
    assert pool.k_q.shape[-1] == Dh // 2      # packed pages

    # behavioral: gathered pool view == dense cache, decode step
    q1 = jax.random.normal(key, (B, 1, H, Dh)) * 0.5
    offs1 = jnp.maximum(lens_a - 1, 0)
    gath = attn.paged_gather(pool, pt, lens_a)
    o_d = attn.pim_attention(q1, dense, PIM, LUT, offs1, out_dtype=jnp.float32)
    o_p = attn.pim_attention(q1, gath, PIM, LUT, offs1, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(o_d), np.asarray(o_p))

    # decode kernel (4-bit pools take the one-page walk: block_k ==
    # page_size)
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


# ---------------------------------------------------------------------------
# scheduler integration
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_setup():
    cfg = get_config("internlm2-1.8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(1, cfg.vocab_size, (4, 24)))}
    return cfg, model, params, prompts


def test_scheduler_kv8_override_bit_identical(smoke_setup):
    """kv_bits=8 (explicit) == no override: the default path is untouched."""
    cfg, model, params, prompts = smoke_setup
    base = serve_lib.generate(model, params, prompts, 10, 128,
                              continuous_batching=True,
                              page_size=16, num_pages=64)
    kv8 = serve_lib.generate(model, params, prompts, 10, 128,
                             continuous_batching=True,
                             page_size=16, num_pages=64, kv_bits=8)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(kv8))


def test_scheduler_kv4_paged_matches_dense(smoke_setup):
    """4-bit behavioral scheduler: paged pool == dense slots, greedy."""
    cfg, model, params, prompts = smoke_setup
    paged = serve_lib.generate(model, params, prompts, 10, 128,
                               continuous_batching=True,
                               page_size=16, num_pages=64, kv_bits=4)
    dense = serve_lib.generate(model, params, prompts, 10, 128,
                               continuous_batching=True, kv_bits=4)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


def test_scheduler_kv4_byte_accounting(smoke_setup):
    """Page + spill byte accounting follows the stored precision: 4-bit
    halves the VALUE bytes (scale planes are f32 at every precision)."""
    cfg, model, params, _ = smoke_setup
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    s8 = serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                             page_size=16, num_pages=16)
    s4 = serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                             page_size=16, num_pages=16, kv_bits=4)
    assert s4.model.cfg.kv_bits == 4
    assert s4.cache["blocks"][0].k_q.shape[-1] == dh // 2
    bpt8 = cfg.num_layers * (2 * hkv * dh + 8 * hkv)
    bpt4 = cfg.num_layers * (2 * hkv * (dh // 2) + 8 * hkv)
    assert s8.stats["kv_bytes_per_token"] == bpt8
    assert s4.stats["kv_bytes_per_token"] == bpt4
    assert s8._page_bytes == 16 * bpt8
    assert s4._page_bytes == 16 * bpt4
    assert bpt4 < bpt8
