"""Chunked prefill + mixed prefill/decode steps (ISSUE 5).

  * `plan_prefill_chunk` covers every prompt token exactly once — hypothesis
    property over random prompt lengths, chunk budgets, page sizes and
    prefix-hit offsets (no skip, no double-write, page-aligned interior
    boundaries whenever reachable)
  * ragged-Q kernels: rows below q_len are bit-identical to an unmasked
    launch, rows at/past it cost zero KV iterations (return_iters probe),
    decode-kernel rows with q_len == 0 contribute exact zeros
  * chunked prefill writes the SAME quantized cache bytes and produces the
    same final-position logits as one monolithic prefill (dense + paged,
    behavioral + kernel)
  * mixed-step Scheduler bit-parity vs the unchunked scheduler: greedy and
    sampled, dense and paged, prefix sharing on and off, kernel path with
    the flash-decode kernel enabled, eviction under a starved pool
  * structural no-stall property: decoding slots keep emitting while a long
    prompt is mid-prefill
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import PIMConfig
from repro.core import attention as attn
from repro.core.attention import expected_kv_block_iters
from repro.data import pipeline as data
from repro.kernels import ops
from repro.kernels.pim_attention import pim_attention_pallas
from repro.kernels.pim_decode import pim_decode_pallas
from repro.models.model_zoo import build_model
from repro.runtime import serve_lib
from repro.runtime.serve_lib import plan_prefill_chunk

PIM = PIMConfig()


# ---------------------------------------------------------------------------
# chunk planner: every prompt token exactly once
# ---------------------------------------------------------------------------
def _chunk_cover(start, p_len, budget, page_size):
    """Drive the planner to completion; returns the list of (s, e) chunks."""
    chunks = []
    pos = start
    while pos < p_len:
        end = plan_prefill_chunk(pos, p_len, budget, page_size)
        chunks.append((pos, end))
        pos = end
        assert len(chunks) <= p_len, "planner failed to advance"
    return chunks


def test_plan_prefill_chunk_property():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(p_len=st.integers(1, 600), start_frac=st.floats(0.0, 1.0),
               budget=st.integers(1, 128), page_size=st.integers(0, 64))
    @hyp.settings(max_examples=300, deadline=None)
    def check(p_len, start_frac, budget, page_size):
        # start models a prefix-hit offset: any position strictly before the
        # prompt end (the scheduler guarantees >= 1 tail token)
        start = min(int(start_frac * p_len), p_len - 1)
        chunks = _chunk_cover(start, p_len, budget, page_size)
        # exact cover of [start, p_len): contiguous, no skip, no overlap
        assert chunks[0][0] == start and chunks[-1][1] == p_len
        for (s0, e0), (s1, e1) in zip(chunks, chunks[1:]):
            assert e0 == s1
        total = sum(e - s for s, e in chunks)
        assert total == p_len - start
        for s, e in chunks:
            assert 1 <= e - s <= budget
        if page_size:
            # interior boundaries are page-aligned whenever a boundary past
            # the chunk start was in reach; otherwise the chunk stayed
            # within its start page (so no page is left half-validated
            # across a page it shares with a LATER chunk)
            for s, e in chunks[:-1]:
                if e % page_size:
                    assert (e // page_size) * page_size <= s

    check()


def test_plan_prefill_chunk_validation():
    with pytest.raises(ValueError):
        plan_prefill_chunk(5, 5, 4)            # start beyond the prompt
    with pytest.raises(ValueError):
        plan_prefill_chunk(0, 5, 0)            # no budget


# ---------------------------------------------------------------------------
# ragged-Q kernels
# ---------------------------------------------------------------------------
def _mixed_cache(key, B, max_len, lens, Hkv, Dh, scale=0.5):
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, max_len, Hkv, Dh)) * scale
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, max_len, Hkv, Dh)) * scale
    cache = attn.cache_write(attn.init_kv_cache(B, max_len, Hkv, Dh),
                             k, v, 0, PIM)
    return cache._replace(length=jnp.asarray(lens, jnp.int32))


def test_ragged_q_prefill_kernel_masking_and_early_out():
    """Rows below q_len are bit-identical to the unmasked launch; q blocks
    at/past q_len run zero KV iterations (a decode row in a wide mixed
    batch pays only its own blocks)."""
    B, max_len, Sq, H, Hkv, Dh, bq, bk = 3, 96, 16, 4, 2, 32, 8, 16
    lens = np.array([70, 33, 48], np.int32)
    q_lens = np.array([16, 1, 7], np.int32)    # chunk / decode / short chunk
    key = jax.random.PRNGKey(0)
    cache = _mixed_cache(key, B, max_len, lens, Hkv, Dh)
    q = jax.random.normal(key, (B, Sq, H, Dh)) * 0.5
    offs = jnp.asarray(lens - q_lens, jnp.int32)
    qq = ops.kernel_attention_layout(q, cache)
    o_m, it_m = pim_attention_pallas(*qq, offs, cache.length, block_q=bq,
                                     block_k=bk, interpret=True,
                                     return_iters=True,
                                     q_len=jnp.asarray(q_lens))
    o_f = pim_attention_pallas(*qq, offs, cache.length, block_q=bq,
                               block_k=bk, interpret=True)
    o_m = np.asarray(o_m).reshape(B, H, Sq, Dh)
    o_f = np.asarray(o_f).reshape(B, H, Sq, Dh)
    per_row = np.asarray(it_m).reshape(B, H, -1).sum(axis=(1, 2))
    for b in range(B):
        ql = int(q_lens[b])
        np.testing.assert_array_equal(o_m[b, :, :ql], o_f[b, :, :ql])
        # q blocks entirely past q_len were skipped -> exact zeros
        skip_from = -(-ql // bq) * bq
        np.testing.assert_array_equal(o_m[b, :, skip_from:], 0.0)
        exp = expected_kv_block_iters(Sq, max_len, int(offs[b]), bq, bk,
                                      causal=True, kv_valid_len=int(lens[b]),
                                      q_valid_len=ql)
        assert per_row[b] == H * exp
    # the decode row (q_len == 1) paid only ceil(kv_len/bk) blocks
    assert per_row[1] == H * -(-int(lens[1]) // bk)


def test_ragged_q_decode_kernel_skip_rows():
    """q_len == 0 rows of the decode-kernel launch run zero KV blocks and
    return exact zeros; q_len == 1 rows are bit-identical to an unmasked
    launch — the mixed step's complementary early-out pair."""
    B, max_len, H, Hkv, Dh, bk = 4, 128, 4, 2, 32, 32
    lens = np.array([90, 17, 64, 33], np.int32)
    q_lens = np.array([1, 0, 1, 0], np.int32)
    key = jax.random.PRNGKey(1)
    cache = _mixed_cache(key, B, max_len, lens, Hkv, Dh)
    q = jax.random.normal(key, (B, 1, H, Dh)) * 0.5
    offs = jnp.maximum(jnp.asarray(lens) - 1, 0)
    qq = ops.kernel_attention_layout(q, cache)
    o_m, it_m = pim_decode_pallas(*qq, offs, cache.length, block_k=bk,
                                  interpret=True, return_iters=True,
                                  q_len=jnp.asarray(q_lens))
    o_f = pim_decode_pallas(*qq, offs, cache.length, block_k=bk,
                            interpret=True)
    o_m = np.asarray(o_m).reshape(B, H, Dh)
    o_f = np.asarray(o_f).reshape(B, H, Dh)
    per_slot = np.asarray(it_m).reshape(B, Hkv, -1).sum(axis=(1, 2))
    for b in range(B):
        if q_lens[b]:
            np.testing.assert_array_equal(o_m[b], o_f[b])
            assert per_slot[b] == Hkv * -(-int(lens[b]) // bk)
        else:
            np.testing.assert_array_equal(o_m[b], 0.0)
            assert per_slot[b] == 0


# ---------------------------------------------------------------------------
# chunked prefill: same cache bytes, same logits
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("internlm2-1.8b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def kernel_model():
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              attn_impl="kernel")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _cache_leaves(cache):
    return jax.tree.leaves(cache)


@pytest.mark.parametrize("impl", ["behavioral", "kernel"])
@pytest.mark.parametrize("paged", [False, True])
def test_chunked_prefill_cache_bytes_and_logits(smoke_model, kernel_model,
                                                impl, paged):
    """Prefilling a prompt in chunks writes bit-identical quantized KV (all
    cache leaves) and yields bit-identical final-position logits vs one
    monolithic prefill — the invariant every mixed-step guarantee rests
    on (K/V quantization and attention are per-token/per-row)."""
    cfg, model, params = kernel_model if impl == "kernel" else smoke_model
    P, max_len, ps = 20, 32, 8
    toks = np.asarray(data.lm_batch(5, 1, P, cfg.vocab_size))

    def fresh():
        if paged:
            return (model.init_cache(1, max_len, ragged=True, page_size=ps,
                                     num_pages=1 + max_len // ps),
                    jnp.asarray([[1, 2, 3, 4]], jnp.int32))
        return model.init_cache(1, max_len, ragged=True), None

    def forward(cache, pages, lo, hi):
        return model.forward_serve(
            params, {"tokens": jnp.asarray(toks[:, lo:hi])}, cache,
            jnp.asarray([lo], jnp.int32),
            seq_lens=jnp.asarray([hi - lo], jnp.int32), pages=pages)

    cache_f, pages = fresh()
    logits_f, cache_f, _ = forward(cache_f, pages, 0, P)
    cache_c, pages = fresh()
    logits_c = None
    for lo, hi in ((0, 7), (7, 8), (8, 16), (16, P)):    # ragged chunk cuts
        logits_c, cache_c, _ = forward(cache_c, pages, lo, hi)
    np.testing.assert_array_equal(np.asarray(logits_f),
                                  np.asarray(logits_c))
    for lf, lc in zip(_cache_leaves(cache_f), _cache_leaves(cache_c)):
        np.testing.assert_array_equal(np.asarray(lf), np.asarray(lc))


# ---------------------------------------------------------------------------
# mixed-step scheduler bit-parity
# ---------------------------------------------------------------------------
def _trace(cfg, seed=1, n=4, width=24):
    full = np.asarray(data.lm_batch(seed, n, width, cfg.vocab_size))
    lens = [5, 17, 24, 9][:n]
    budgets = [4, 7, 10, 13][:n]
    return [(full[i][: lens[i]].tolist(), budgets[i]) for i in range(n)]


def _run(model, params, trace, **kw):
    sched = serve_lib.Scheduler(model, params, **kw)
    rids = [sched.submit(p, t) for p, t in trace]
    res = sched.run()
    return [res[r] for r in rids], sched


@pytest.mark.parametrize("budget", [3, 8, 64])
def test_mixed_parity_behavioral_dense(smoke_model, budget):
    cfg, model, params = smoke_model
    trace = _trace(cfg)
    base, _ = _run(model, params, trace, max_batch_slots=2, max_len=64)
    mixed, sched = _run(model, params, trace, max_batch_slots=2, max_len=64,
                        mixed_steps=True, prefill_chunk_budget=budget)
    assert mixed == base
    assert sched.prefill_tokens_computed == sum(len(p) for p, _ in trace)


@pytest.mark.parametrize("dispatch", ["fused", "paired"])
def test_mixed_parity_behavioral_paged(smoke_model, dispatch):
    """Both mixed-step dispatch shapes — the one (B, L) rectangle and the
    chunk-wave/decode-scan pair — produce the unchunked scheduler's exact
    tokens."""
    cfg, model, params = smoke_model
    trace = _trace(cfg)
    base, s0 = _run(model, params, trace, max_batch_slots=2, max_len=64,
                    page_size=8)
    mixed, s1 = _run(model, params, trace, max_batch_slots=2, max_len=64,
                     page_size=8, mixed_steps=True, prefill_chunk_budget=8,
                     mixed_dispatch=dispatch)
    assert mixed == base
    assert s1.prefill_tokens_computed == s0.prefill_tokens_computed


def test_paired_dispatch_requires_paged(smoke_model):
    cfg, model, params = smoke_model
    with pytest.raises(ValueError):
        serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                            mixed_steps=True, mixed_dispatch="paired")
    with pytest.raises(ValueError):
        serve_lib.Scheduler(model, params, max_batch_slots=2, max_len=64,
                            mixed_steps=True, mixed_dispatch="bogus",
                            page_size=8)


def test_mixed_parity_sampled(smoke_model):
    """Per-(request, token-index) sampling keys make chunked admission
    bit-identical to the unchunked scheduler even at temperature > 0."""
    cfg, model, params = smoke_model
    trace = _trace(cfg, seed=2)
    kw = dict(max_batch_slots=2, max_len=64, temperature=0.8, top_k=12,
              top_p=0.9)
    base, _ = _run(model, params, trace, rng=jax.random.PRNGKey(7), **kw)
    mixed, _ = _run(model, params, trace, rng=jax.random.PRNGKey(7),
                    mixed_steps=True, prefill_chunk_budget=6, **kw)
    rerun, _ = _run(model, params, trace, rng=jax.random.PRNGKey(7),
                    mixed_steps=True, prefill_chunk_budget=6, **kw)
    assert mixed == base                      # chunked == unchunked
    assert mixed == rerun                     # and deterministic
    diff, _ = _run(model, params, trace, rng=jax.random.PRNGKey(8),
                   mixed_steps=True, prefill_chunk_budget=6, **kw)
    assert diff != mixed                      # the key actually matters


def test_mixed_parity_kernel_paths(kernel_model):
    """Kernel path with the flash-decode kernel enabled: decode rows of a
    mixed step route through the SAME kernel dispatch an unchunked decode
    step uses, so outputs stay bit-identical — dense and paged, fused and
    paired."""
    cfg, model, params = kernel_model
    base_t = np.asarray(data.lm_batch(3, 3, 24, cfg.vocab_size))
    trace = [(base_t[0, :9].tolist(), 5), (base_t[1, :20].tolist(), 4),
             (base_t[2, :6].tolist(), 6)]
    kw = dict(max_batch_slots=2, max_len=48, decode_chunk=4)
    base, _ = _run(model, params, trace, **kw)
    mixed, _ = _run(model, params, trace, mixed_steps=True,
                    prefill_chunk_budget=8, **kw)
    assert mixed == base
    basep, _ = _run(model, params, trace, page_size=8, **kw)
    mixedp, _ = _run(model, params, trace, page_size=8, mixed_steps=True,
                     prefill_chunk_budget=8, **kw)
    assert mixedp == basep == base
    paired, _ = _run(model, params, trace, page_size=8, mixed_steps=True,
                     prefill_chunk_budget=8, mixed_dispatch="paired", **kw)
    assert paired == base


def test_forward_serve_decode_rows_matches_separate_dispatches(kernel_model):
    """One mixed forward (decode_rows marking the single-token rows) must
    reproduce, bit-for-bit, what separate prefill-chunk and decode
    dispatches produce — logits AND cache bytes (the kernel-path fused
    rectangle's correctness contract)."""
    cfg, model, params = kernel_model
    ps, max_len, n_pages = 8, 32, 9
    toks = np.asarray(data.lm_batch(9, 2, 16, cfg.vocab_size))

    def fresh():
        cache = model.init_cache(2, max_len, ragged=True, page_size=ps,
                                 num_pages=n_pages)
        pages = jnp.asarray([[1, 2, 3, -1], [4, 5, 6, -1]], jnp.int32)
        return cache, pages

    # seed both rows with 8 tokens of KV
    def seed(cache, pages):
        _, cache, _ = model.forward_serve(
            params, {"tokens": jnp.asarray(toks[:, :8])}, cache,
            jnp.zeros(2, jnp.int32), seq_lens=jnp.asarray([8, 8]),
            pages=pages)
        return cache

    # mixed: row 0 decodes token 8, row 1 prefills chunk [8, 12)
    batch = np.zeros((2, 4), np.int32)
    batch[0, 0] = toks[0, 8]
    batch[1, :4] = toks[1, 8:12]
    cache_m, pages = fresh()
    cache_m = seed(cache_m, pages)
    logits_m, cache_m, _ = model.forward_serve(
        params, {"tokens": jnp.asarray(batch)}, cache_m,
        jnp.asarray([8, 8], jnp.int32), seq_lens=jnp.asarray([1, 4]),
        pages=pages, decode_rows=jnp.asarray([True, False]))
    # separate: the decode row as its own Sq==1 dispatch, the chunk row as
    # its own prefill dispatch (what the unchunked scheduler would run)
    cache_s, pages = fresh()
    cache_s = seed(cache_s, pages)
    logits_d, cache_s, _ = model.forward_serve(
        params, {"tokens": jnp.asarray(batch[:, :1])}, cache_s,
        jnp.asarray([8, 0], jnp.int32), seq_lens=jnp.asarray([1, 0]),
        pages=pages)
    logits_p, cache_s, _ = model.forward_serve(
        params, {"tokens": jnp.asarray(batch)}, cache_s,
        jnp.asarray([0, 8], jnp.int32), seq_lens=jnp.asarray([0, 4]),
        pages=pages)
    np.testing.assert_array_equal(np.asarray(logits_m[0]),
                                  np.asarray(logits_d[0]))
    np.testing.assert_array_equal(np.asarray(logits_m[1]),
                                  np.asarray(logits_p[1]))

    def strip_trash(a):
        # the trash page (page 0) absorbs each dispatch's masked writes —
        # its bytes legitimately differ and are never observable
        a = np.asarray(a)
        ax = [i for i, d in enumerate(a.shape) if d == n_pages]
        if not ax:
            return a
        sl = [slice(None)] * a.ndim
        sl[ax[0]] = slice(1, None)
        return a[tuple(sl)]

    for lm, ls in zip(jax.tree.leaves(cache_m), jax.tree.leaves(cache_s)):
        np.testing.assert_array_equal(strip_trash(lm), strip_trash(ls))


def test_mixed_prefix_sharing_parity_and_deferral(smoke_model):
    """Prefix sharing under chunked admission: same hits, same skipped
    prefill tokens, bit-identical outputs; a request whose prefix is still
    mid-chunked-prefill defers and then maps the published pages."""
    cfg, model, params = smoke_model
    base = np.asarray(data.lm_batch(0, 7, 48, cfg.vocab_size))
    prefix = base[6, :32].tolist()
    trace = [(prefix + base[i, : 5 + i].tolist(), 6 + i) for i in range(5)]
    kw = dict(max_batch_slots=3, max_len=96, page_size=16, num_pages=40)
    off, _ = _run(model, params, trace, **kw)
    on, s_on = _run(model, params, trace, prefix_sharing=True, **kw)
    mix_off, _ = _run(model, params, trace, mixed_steps=True,
                      prefill_chunk_budget=16, **kw)
    mix_on, s_mix = _run(model, params, trace, prefix_sharing=True,
                         mixed_steps=True, prefill_chunk_budget=16, **kw)
    assert mix_off == off and mix_on == on and on == off
    assert s_mix.prefix_hits == s_on.prefix_hits == len(trace) - 1
    assert s_mix.prefix_hit_tokens == s_on.prefix_hit_tokens
    assert s_mix.prefill_tokens_computed == s_on.prefill_tokens_computed
    # two identical prompts with ONE free slot's worth of pages each: the
    # second sees the first's registration keys in flight, defers, and maps
    # the pages after completion (one physical prefix, one full prefill)
    t2 = [(prefix + base[0, :3].tolist(), 4),
          (prefix + base[0, :3].tolist(), 4)]
    r2, s2 = _run(model, params, t2, prefix_sharing=True, mixed_steps=True,
                  prefill_chunk_budget=8, **kw)
    assert r2[0] == r2[1]
    assert s2.prefix_hits == 1
    assert s2.prefix_hit_tokens == 32


def test_mixed_eviction_starved_pool(smoke_model):
    """A pool too small for the offered load forces stalls/evictions while
    chunked prefill is interleaving — continuations must still resume the
    exact greedy streams."""
    cfg, model, params = smoke_model
    full = np.asarray(data.lm_batch(4, 3, 24, cfg.vocab_size))
    trace = [(full[i, : 16 + 4 * i].tolist(), 14) for i in range(3)]
    kw = dict(max_batch_slots=3, max_len=48, page_size=8,
              num_pages=1 + 48 // 8 + 3)      # < worst-case demand
    base, s0 = _run(model, params, trace, **kw)
    mixed, s1 = _run(model, params, trace, mixed_steps=True,
                     prefill_chunk_budget=8, **kw)
    assert mixed == base
    assert s1.n_evictions > 0                 # the starvation actually bit


def test_mixed_steps_interleave_decode_with_long_prefill(smoke_model):
    """The no-stall property itself: while a long prompt is mid-prefill,
    the already-decoding slot keeps emitting every step (the unchunked
    scheduler emits nothing for it until the prefill dispatch returns)."""
    cfg, model, params = smoke_model
    full = np.asarray(data.lm_batch(6, 2, 64, cfg.vocab_size))
    sched = serve_lib.Scheduler(model, params, max_batch_slots=2,
                                max_len=128, decode_chunk=4,
                                mixed_steps=True, prefill_chunk_budget=8)
    ra = sched.submit(full[0, :8].tolist(), 40)
    got = {ra: []}
    while len(got[ra]) < 3:                   # slot 0 is decoding steadily
        got[ra] += sched.step().get(ra, [])
    rc = sched.submit(full[1].tolist(), 4)    # 64-token prompt arrives
    got[rc] = []
    steps_before_c, a_during = 0, 0
    while not got[rc]:
        em = sched.step()
        for rid, toks in em.items():
            got[rid] += toks
        if not got[rc]:
            steps_before_c += 1
            a_during += len(em.get(ra, []))
    # 64 prompt tokens at budget 8 -> 8 chunk steps; the decoding slot
    # advanced on (at least) every step but the last chunk's
    assert steps_before_c >= 7
    assert a_during >= steps_before_c - 1
    for rid, toks in sched.run().items():     # drain the rest
        got[rid] += toks
    p = {"tokens": jnp.asarray(full[1:2])}
    ref = np.asarray(serve_lib.greedy_generate(model, params, p, 4, 128))[0]
    np.testing.assert_array_equal(np.asarray(got[rc]), ref)
