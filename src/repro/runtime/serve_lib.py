"""Serving step builders: batched prefill + decode over the PIM KV cache.

The serve path is the paper-faithful dataflow: weights loaded once (int8 in
the PIM macros == TP-sharded on device), K/V quantized on write, LUT softmax.
`serve_step` here is what the decode_32k / long_500k dry-run cells lower.

Two generation paths:

  * `generate` (classic): equal-length prompts, scan-fused decode — the whole
    token loop is ONE `lax.scan` inside one jit with the KV cache donated.
  * `Scheduler` (ragged continuous batching): the KV cache is a set of batch
    SLOTS with per-slot lengths; queued requests are admitted into free
    slots, prefilled left-aligned in a padded sub-batch and scatter-inserted,
    decoded together in fused chunk-scans where every slot masks/early-outs
    against its OWN length, and retired on EOS / token budget — at which
    point the slot is immediately reusable.  `generate(...,
    continuous_batching=True)` is a thin wrapper over one Scheduler run.
    With `page_size > 0` the slots share a PAGED pool (vLLM-style): page-
    granular admission, lazy page allocation at decode boundaries, free-on-
    retire — one long sequence no longer pins a whole max_len buffer.
    `prefix_sharing=True` adds refcounted page sharing: requests with a
    common page-aligned prompt prefix map the SAME physical pages (and
    skip the shared prefill), diverging via copy-on-write.
    `mixed_steps=True` chunks admission prefill: instead of one monolithic
    prompt dispatch that stalls every decoding slot, each scheduler step is
    one MIXED batch where decoding slots contribute their next token and
    prefilling slots the next page-aligned chunk of their prompt (at most
    `prefill_chunk_budget` prefill tokens per step) — time between tokens
    stays bounded by the chunk budget, not by the longest queued prompt.

Sampling keys: the Scheduler derives every sampled token's PRNG key from
(rng, request id, token index) via `fold_in`, NOT from a serially split
stream — a request's sampled tokens are a pure function of the seed and its
own stream position.  That is what makes chunked admission, eviction
continuations, and any interleaving of mixed steps bit-identical to the
unchunked scheduler even at temperature > 0.

Sharding note: these builders use plain jit with donated caches; partitioning
propagates from the inputs — the launch layer device_puts params/caches with
the DESIGN.md §4 specs (sharding.param_shardings / sharding.cache_specs).
"""
from __future__ import annotations

import collections
import functools
import os
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses

from repro.configs.base import ModelConfig
from repro.core.attention import TRASH_PAGE
from repro.models import transformer as T
from repro.models.model_zoo import Model, build_model
from repro.runtime.fault import CrashInjected, FaultPlan


def kv_bytes_per_token(cfg: ModelConfig) -> int:
    """Device bytes one cached token costs across all layers: K + V values
    at `cfg.kv_bits` precision (packed two-per-byte at 4) plus the two f32
    absmax scale planes, which exist at every precision."""
    hkv = cfg.num_kv_heads
    value_bytes = 2 * hkv * (cfg.resolved_head_dim * cfg.kv_bits // 8)
    scale_bytes = 2 * 4 * hkv
    return cfg.num_layers * (value_bytes + scale_bytes)


# ---------------------------------------------------------------------------
# typed admission results
# ---------------------------------------------------------------------------
class SubmitError(ValueError):
    """Base of the typed `Scheduler.submit` rejections: the request can
    NEVER be served (malformed), as opposed to `Overloaded` (try later)."""


class EmptyPrompt(SubmitError):
    """Rejected: the prompt has no tokens (nothing to condition on)."""


class InvalidBudget(SubmitError):
    """Rejected: `max_new_tokens` <= 0 (the scheduler would otherwise emit
    one token anyway — every admission samples from the prefill logits)."""


class PromptTooLong(SubmitError):
    """Rejected: the prompt can never fit — it reaches `max_len` (no room
    for even one generated token) or needs more pages than the pool owns.
    Without this check such a request would sit at the queue head forever,
    wedging admission for everyone behind it (FCFS never skips)."""


class Overloaded(RuntimeError):
    """Backpressure: the bounded admission queue (`max_queue`) is full.
    Transient — the caller should shed load or retry later; the scheduler
    counts the rejection in `stats['rejections']`."""


class AuditError(AssertionError):
    """`Scheduler.audit()` found a broken invariant: a page refcount that
    does not match its holders (slot rows + directory entries + victim
    pool), an orphaned/double-freed page, or an inconsistent page table."""


@functools.lru_cache(maxsize=64)
def make_prefill_step(model: Model) -> Callable:
    """prefill(params, batch, cache) -> (logits_last, cache, enc_out)."""
    def step(params, batch, cache):
        return model.forward_serve(params, batch, cache, 0)

    return jax.jit(step, donate_argnums=(2,))


@functools.lru_cache(maxsize=64)
def make_decode_step(model: Model) -> Callable:
    """decode(params, tokens, cache, offset, enc_out) -> (logits, cache)."""
    def step(params, batch, cache, offset, enc_out):
        logits, cache, _ = model.forward_serve(params, batch, cache, offset,
                                               enc_out=enc_out)
        return logits, cache

    return jax.jit(step, donate_argnums=(2,))


def sample_logits(logits: jax.Array, key: Optional[jax.Array],
                  temperature: float = 0.0, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """(B, V) logits -> (B,) token ids.

    temperature == 0 is greedy (key may be None); otherwise temperature
    softmax sampling, optionally restricted to the top_k logits and/or the
    top-p (nucleus) probability mass.  top_k >= V is clipped to V (i.e.
    unrestricted); top_k == 1 is greedy regardless of temperature (the only
    non-(-inf) logit is the max).  top_p >= 1 is a no-op (bit-identical to
    not passing it); top_p -> 0 keeps only the argmax token, i.e. greedy
    (probability ties at the nucleus boundary are broken by token id, so
    the kept mass never overshoots by more than the boundary token).
    top_p composes with top_k: the nucleus is taken over the already
    top_k-truncated distribution.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    l = _truncate_logits(logits.astype(jnp.float32) / temperature,
                         top_k, top_p)
    return jax.random.categorical(key, l, axis=-1)


def _truncate_logits(l: jax.Array, top_k: int, top_p: float) -> jax.Array:
    """top_k / nucleus truncation over temperature-scaled f32 logits
    (masked entries -> -inf); last axis is the vocabulary, any leading
    batch shape.  Shared by `sample_logits` and the speculative verifier
    so accept probabilities and residual resamples are computed against
    the EXACT truncated distribution ancestral sampling draws from.

    Nucleus rule: keep the shortest descending-probability prefix whose
    exclusive cumulative mass is below top_p (the boundary token is
    included, so the set is never empty — top_p -> 0 keeps exactly one
    max token, and f32 cumsum rounding can never collapse the set to
    greedy).  Masking happens in SORTED space and is scattered back
    through the inverse permutation, so probability ties at the boundary
    never drag extra mass in.
    """
    if top_k:
        k = min(int(top_k), l.shape[-1])
        if k < l.shape[-1]:
            kth = jax.lax.top_k(l, k)[0][..., -1:]
            l = jnp.where(l < kth, -jnp.inf, l)
    if top_p < 1.0:
        probs = jax.nn.softmax(l, axis=-1)
        order = jnp.argsort(-probs, axis=-1)               # descending
        sp = jnp.take_along_axis(probs, order, axis=-1)
        exclusive = jnp.cumsum(sp, axis=-1) - sp
        keep_sorted = (exclusive < top_p).at[..., 0].set(True)
        keep = jnp.take_along_axis(keep_sorted, jnp.argsort(order, axis=-1),
                                   axis=-1)
        l = jnp.where(keep, l, -jnp.inf)
    return l


@functools.lru_cache(maxsize=64)
def make_generate_fn(model: Model, prompt_len: int, max_new_tokens: int,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 1.0) -> Callable:
    """Build the scan-fused decode program (classic equal-length path).

    Returns generate(params, tok0, cache, rng, enc_out) -> (B, T) ids where
    `tok0` is the (B, 1) token sampled from the prefill logits.  The whole
    token loop is one `lax.scan` with the cache donated: per-token work is a
    single already-compiled device step, which is what makes the decode
    kernel's split-K grid the only per-token cost.

    lru_cached on (model, shape, sampling) so repeated `generate` calls with
    the same Model instance reuse the traced/compiled program instead of
    paying the scan retrace per call.
    """
    def generate(params, tok0, cache, rng, enc_out):
        def body(carry, t):
            tok, cache, key = carry
            logits, cache, _ = model.forward_serve(
                params, {"tokens": tok}, cache, prompt_len + t,
                enc_out=enc_out)
            key, sub = jax.random.split(key)
            nxt = sample_logits(logits, sub, temperature, top_k,
                                top_p)[:, None]
            return (nxt, cache, key), tok[:, 0]

        (_, cache, _), toks = jax.lax.scan(
            body, (tok0, cache, rng), jnp.arange(max_new_tokens))
        return jnp.moveaxis(toks, 0, 1)                      # (B, T)

    return jax.jit(generate, donate_argnums=(2,))


# ===========================================================================
# ragged continuous batching
# ===========================================================================
def _row_keys(base_key, rids, gens):
    """Per-row sampling keys: fold (request id, generated-token index) into
    the scheduler's base key.  A request's i-th generated token always
    samples with the SAME key no matter which dispatch computes it —
    admission prefill, a mixed step, a decode chunk-scan, or the re-prefill
    of an eviction continuation."""
    fold = lambda r, g: jax.random.fold_in(jax.random.fold_in(base_key, r), g)
    return jax.vmap(fold)(jnp.maximum(jnp.asarray(rids, jnp.int32), 0),
                          jnp.asarray(gens, jnp.int32))


def sample_logits_per_row(logits: jax.Array, keys, temperature: float = 0.0,
                          top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """`sample_logits` with an independent PRNG key per batch row (keys:
    (B,) stacked keys from `_row_keys`; ignored when greedy)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.vmap(
        lambda l, k: sample_logits(l[None], k, temperature, top_k, top_p)[0]
    )(logits, keys)


def _moe_stack(model: Model) -> bool:
    """MoE stacks' step programs return one more output, last: the step's
    MoE load counts, int32 (2,), summed over its forwards and layers (see
    `transformer.forward_serve(moe_load=True)`)."""
    return "moe" in model.cfg.block_pattern


def scheduler_supported(cfg: ModelConfig) -> bool:
    """The slot scheduler serves pure attention stacks: recurrent/ring states
    can't be length-masked per slot (their state mixes padded positions in),
    and encoder-decoder archs need per-request encoder features."""
    kinds = set(cfg.block_pattern)
    return (not cfg.is_encoder_decoder
            and kinds <= {"attn", "moe"}
            and not cfg.window)


@functools.lru_cache(maxsize=64)
def make_ragged_prefill_fn(model: Model, n: int, pad_len: int, max_len: int,
                           temperature: float = 0.0,
                           top_k: int = 0, top_p: float = 1.0) -> Callable:
    """Admission prefill: n left-aligned prompts padded to pad_len are run
    through one forward with per-row valid lengths (padding K/V beyond a
    row's length is written but never advertised), each row's first token is
    sampled from its LAST VALID position's logits (per-row (rid, index)
    keys), and the sub-batch cache is scatter-inserted into the big cache's
    free slots.  The per-row `fin` output flags rows whose logits were all
    finite; a poisoned (NaN/Inf) row samples -1 and is quarantined by the
    host (`status="poisoned"`) instead of emitting garbage.
    """
    moe = _moe_stack(model)

    def prefill(params, tokens, lens, big_cache, slots, rids, gens, base_key):
        sub = model.init_cache(n, max_len, ragged=True)
        offs = jnp.zeros((n,), jnp.int32)
        logits, sub, _, *load = model.forward_serve(
            params, {"tokens": tokens}, sub, offs, seq_lens=lens,
            moe_load=moe)
        fin = jnp.all(jnp.isfinite(logits), axis=-1)
        tok0 = sample_logits_per_row(logits, _row_keys(base_key, rids, gens),
                                     temperature, top_k, top_p)
        tok0 = jnp.where(fin, tok0, -1)
        return (T.cache_scatter(big_cache, sub, slots), tok0, fin, *load)

    return jax.jit(prefill, donate_argnums=(3,))


@functools.lru_cache(maxsize=64)
def make_paged_prefill_fn(model: Model, n: int, pad_len: int,
                          temperature: float = 0.0,
                          top_k: int = 0, top_p: float = 1.0) -> Callable:
    """Paged admission prefill: n left-aligned prompts write STRAIGHT into
    the shared page pool through their slots' page-table rows — no sub-batch
    cache, no scatter-insert (the pages were assigned by the host allocator,
    so the write destinations are already this wave's own pages).

    `offs` is the per-row absolute position of the chunk's first token
    (all zeros for a full-prompt prefill).  With prefix sharing a row's
    leading page-table entries already hold the shared prefix KV, `offs`
    is the shared token count, and only the divergent TAIL runs through
    this forward — row b's queries attend to positions [0, offs_b +
    lens_b) through the table, so the tail sees the shared prefix exactly
    as a full prefill would (same quantized bytes -> bit-identical
    logits).
    """
    moe = _moe_stack(model)

    def prefill(params, tokens, lens, big_cache, pages, offs, rids, gens,
                base_key):
        logits, big_cache, _, *load = model.forward_serve(
            params, {"tokens": tokens}, big_cache,
            jnp.asarray(offs, jnp.int32), seq_lens=lens, pages=pages,
            moe_load=moe)
        fin = jnp.all(jnp.isfinite(logits), axis=-1)
        tok0 = sample_logits_per_row(logits, _row_keys(base_key, rids, gens),
                                     temperature, top_k, top_p)
        tok0 = jnp.where(fin, tok0, -1)
        return (big_cache, tok0, fin, *load)

    return jax.jit(prefill, donate_argnums=(3,))


@functools.lru_cache(maxsize=64)
def make_page_copy_fn(model: Model) -> Callable:
    """Copy-on-write device step: copy pages src[i] -> dst[i] in every
    layer's pool (cache donated — the copy is in-place on device)."""
    def copy(cache, src, dst):
        return T.cache_copy_pages(cache, src, dst)

    return jax.jit(copy, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def make_page_fetch_fn(model: Model) -> Callable:
    """Device half of a page SPILL: gather the named physical pages out of
    every layer's pool into a compact page-major tree the caller
    `device_get`s into the host victim pool.  The cache is NOT donated —
    the pool keeps serving the surviving slots while the bytes drain.
    Callers pad `pages` to a power-of-two width with `TRASH_PAGE` entries
    so the gather compiles O(log n) shapes, mirroring `_apply_copies`."""
    def fetch(cache, pages):
        return T.cache_fetch_pages(cache, pages)

    return jax.jit(fetch)


@functools.lru_cache(maxsize=64)
def make_page_restore_fn(model: Model) -> Callable:
    """Device half of a page RESTORE (cache donated): scatter a previously
    fetched page tree into freshly allocated physical pages — the inverse
    of `make_page_fetch_fn`, bit-exact because whole pages of already
    quantized K/V bytes round-trip untouched.  `pages` carries the same
    power-of-two `TRASH_PAGE` padding as the fetch (padding lanes write
    into the reserved trash page, a no-op by construction)."""
    def restore(cache, pages, data):
        return T.cache_restore_pages(cache, pages, data)

    return jax.jit(restore, donate_argnums=(0,))


@functools.lru_cache(maxsize=64)
def make_ragged_decode_fn(model: Model, chunk: int, temperature: float,
                          top_k: int, eos_id: Optional[int],
                          max_len: int, top_p: float = 1.0) -> Callable:
    """Fused ragged decode: `chunk` tokens for ALL slots in one lax.scan.

    Every step writes each active slot's token at its own cache position,
    attends with per-slot kv_len (inactive slots cost zero KV partitions in
    the decode kernel), samples, and retires rows that hit EOS / their token
    budget / the cache capacity — retired rows' lengths drop to 0 so the rest
    of the chunk skips them entirely.

    Paged callers pass a trailing (B, max_pages) page table (loop-invariant
    across the chunk: the host allocator guarantees the table covers
    `lengths + chunk` tokens per active slot before the call) and the cache
    is the shared page pool; dense callers simply omit it.

    Sampling uses per-(request, token-index) keys (`_row_keys`): `rids` is
    the (B,) request id per slot and `gens` the per-slot count of tokens
    generated so far, incremented in-scan only while a row stays active.

    Poison handling: `poison` (B,) injects NaN into the named rows' logits
    at the chunk's first step (the fault hook's seam), and ANY non-finite
    logit row — injected or model-produced — is quarantined in-scan: it
    emits nothing, deactivates, and is reported in the `pois` output so the
    host retires just that request (`status="poisoned"`).  Neighbors' rows
    never see the poison (logit rows are batch-independent), so their
    streams stay bit-identical.

    Returns decode(params, tok, cache, lengths, active, remaining, rids,
    gens, base_key, poison[, pages]) -> (tok, cache, lengths, active,
    remaining, toks (chunk, B), emitted (chunk, B) bool, pois (B,) bool),
    and a MoE stack's load counts last (`_moe_stack`).
    """
    eos = -2 if eos_id is None else int(eos_id)   # -2 never matches a token
    moe = _moe_stack(model)

    def decode(params, tok, cache, lengths, active, remaining, rids, gens,
               base_key, poison, pages=None):
        def body(carry, t):
            tok, cache, lengths, active, remaining, gens, pois, *load = carry
            act = active.astype(jnp.int32)
            logits, cache, _, *step_load = model.forward_serve(
                params, {"tokens": tok[:, None]}, cache, lengths,
                seq_lens=act, pages=pages, moe_load=moe)
            logits = jnp.where((poison & (t == 0))[:, None], jnp.nan, logits)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
            nxt = sample_logits_per_row(logits,
                                        _row_keys(base_key, rids, gens),
                                        temperature, top_k, top_p)
            nxt = jnp.where(active & fin, nxt, -1)
            new_len = lengths + act
            new_active = (active & fin & (nxt != eos) & (remaining > 1)
                          & (new_len < max_len))
            # retired slots advertise length 0 from the NEXT step on: the
            # decode kernel's per-slot early-out then runs zero partitions
            lengths = jnp.where(active & ~new_active, 0, new_len)
            carry = (nxt, cache, lengths, new_active, remaining - act,
                     gens + act, pois | (active & ~fin),
                     *(a + b for a, b in zip(load, step_load)))
            return carry, (nxt, active & fin)

        load0 = (jnp.zeros((2,), jnp.int32),) if moe else ()
        carry, (toks, emitted) = jax.lax.scan(
            body, (tok, cache, lengths, active, remaining, gens,
                   jnp.zeros_like(active), *load0),
            jnp.arange(chunk))
        return carry[:5] + (toks, emitted) + carry[6:]

    return jax.jit(decode, donate_argnums=(2,))


@functools.lru_cache(maxsize=64)
def make_mixed_step_fn(model: Model, n: int, pad_len: int,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0) -> Callable:
    """One MIXED scheduler step: every slot row carries either one decode
    token (decode_rows[b], seq_lens[b] == 1, offs[b] == current fill), a
    prefill chunk (seq_lens[b] tokens of its prompt at absolute offset
    offs[b]), or nothing (seq_lens[b] == 0 — idle/stalled, zero compute).

    One forward advances every row's cache; attention routes decode rows
    through the split-K decode launch and chunk rows through the ragged-Q
    prefill launch inside the same program (`blocks._mixed_attend`), so
    each row is bit-identical to its unchunked dispatch.  A token is
    sampled for every row from its last valid position with per-(rid,
    index) keys — the host keeps it only for decode rows and for rows whose
    chunk completed their prompt (their tok0), and discards the rest.

    Returns step(params, toks, cache, offs, seq_lens, decode_rows, rids,
    gens, base_key, poison[, pages]) -> (cache, tok (n,), fin (n,) bool);
    `poison` NaN-injects the named rows' logits and `fin` reports which
    rows stayed finite — the host quarantines ~fin rows (`"poisoned"`).
    A MoE stack's load counts follow last (`_moe_stack`).
    """
    moe = _moe_stack(model)

    def step(params, toks, cache, offs, seq_lens, decode_rows, rids, gens,
             base_key, poison, pages=None):
        logits, cache, _, *load = model.forward_serve(
            params, {"tokens": toks}, cache, jnp.asarray(offs, jnp.int32),
            seq_lens=seq_lens, pages=pages, decode_rows=decode_rows,
            moe_load=moe)
        logits = jnp.where(poison[:, None], jnp.nan, logits)
        fin = jnp.all(jnp.isfinite(logits), axis=-1)
        tok = sample_logits_per_row(logits, _row_keys(base_key, rids, gens),
                                    temperature, top_k, top_p)
        tok = jnp.where(fin, tok, -1)
        return (cache, tok, fin, *load)

    return jax.jit(step, donate_argnums=(2,))


# ===========================================================================
# speculative decoding: self-speculative drafts + batched verification
# ===========================================================================
def propose_draft_tokens(context: Sequence[int], k: int, *,
                         max_ngram: int = 3,
                         eos_id: Optional[int] = None) -> List[int]:
    """Self-speculative n-gram (prompt-lookup) draft proposer.

    Finds the RIGHTMOST earlier occurrence of the longest suffix n-gram
    (down from `max_ngram` to 1 token) of `context` (the slot's own
    prompt + generated tokens — nothing else is ever consulted) and
    proposes the tokens that followed it.  When the match sits near the
    end of the context — a tight cycle, where only a token or two follow
    it — the lookup is re-run on context + draft-so-far, extending the
    draft autoregressively (the lookup IS the draft model) until `k`
    tokens are proposed or no suffix repeats.  Returns [] when the
    context repeats nothing — the slot then runs a plain 1-token decode
    step.  Proposals are cut at the first EOS INCLUSIVE (an accepted EOS
    retires the request; drafting past it would waste verify columns),
    and the function is a pure deterministic lookup: a fixed context
    always yields the same proposal.
    """
    ctx = [int(t) for t in context]
    if k <= 0 or len(ctx) < 2:
        return []
    out: List[int] = []
    while len(out) < k:
        ext = ctx + out
        n = len(ext)
        chunk: List[int] = []
        for g in range(min(int(max_ngram), n - 1), 0, -1):
            suffix = ext[n - g:]
            for i in range(n - g - 1, -1, -1):
                if ext[i:i + g] == suffix:
                    chunk = ext[i + g: i + g + (k - len(out))]
                    break
            if chunk:
                break
        if not chunk:
            break
        if eos_id is not None and int(eos_id) in chunk:
            out += chunk[: chunk.index(int(eos_id)) + 1]
            break
        out += chunk
    return out


def _row_key_grid(base_key, rids, gens, P: int):
    """(B, P) sampling-key grid: column j of row b is EXACTLY the
    `_row_keys` key for generated-token index gens[b] + j.  The
    speculative verifier's column-j accept coin / resample therefore
    consumes the same per-(request, token-index) key stream the
    non-speculative scheduler uses, which is what makes temperature > 0
    speculative runs seed-deterministic."""
    col = jnp.arange(P, dtype=jnp.int32)

    def row(r, g):
        kr = jax.random.fold_in(base_key, r)
        return jax.vmap(lambda j: jax.random.fold_in(kr, j))(g + col)

    return jax.vmap(row)(jnp.maximum(jnp.asarray(rids, jnp.int32), 0),
                         jnp.asarray(gens, jnp.int32))


@functools.lru_cache(maxsize=64)
def make_spec_step_fn(model: Model, n: int, pad_len: int, verify_len: int,
                      temperature: float = 0.0, top_k: int = 0,
                      top_p: float = 1.0) -> Callable:
    """One SPECULATIVE scheduler step: decode rows carry their current
    token plus up to `verify_len - 1` drafted tokens (seq_lens[b] = 1 +
    k_b), prefill-chunk rows their chunk, idle rows nothing.  One forward
    verifies every drafted position — attention routes decode rows
    through the multi-row split-K decode launch (`force_decode_kernel`),
    so each drafted position is scored bit-identically to the 1-token
    decode step it replaces — and the model returns logits at ALL
    `verify_len` columns (`logit_positions`).

    Per-row accept rule over the draft columns (column j scores the token
    drafted at input column j + 1):

      * temperature == 0 — longest prefix of drafts matching the exact
        argmax chain; the emitted tokens are argmax[0..acc], so greedy
        streams are bit-identical to the non-speculative scheduler.
      * temperature > 0 — rejection sampling against the truncated
        (top_k/top_p) distribution p~: the point-mass draft d_j is
        accepted with probability p~_j(d_j) (coin = uniform under
        fold_in(key_j, 1)); the first rejection resamples from the
        residual p~_j with d_j masked out (fold_in(key_j, 2)), which
        preserves the output distribution exactly.  All-accepted rows
        sample a BONUS token from the last column with the UNMODIFIED
        key_j — so rows with zero drafts (and prefill-chunk rows, whose
        columns all point at their last valid position) reduce to the
        plain mixed-step sampler bit-for-bit.

    Every row emits acc + 1 tokens.  KV for rejected drafts was written
    but is never advertised (the host re-advertises only the accepted
    length — the same ragged-length contract that makes mixed-step
    padding writes harmless), so later writes overwrite it.

    Returns step(params, toks, cache, offs, seq_lens, decode_rows, rids,
    gens, base_key, poison[, pages]) -> (cache, out (n, verify_len),
    n_emit (n,), fin (n,) bool) where row b's emitted tokens are
    out[b, :n_emit[b]]; `poison` NaN-injects the named rows' logits and
    the host discards every token of a ~fin row (quarantine).  A MoE
    stack's load counts follow last (`_moe_stack`).
    """
    P = int(verify_len)
    moe = _moe_stack(model)

    def step(params, toks, cache, offs, seq_lens, decode_rows, rids, gens,
             base_key, poison, pages=None):
        sl = jnp.asarray(seq_lens, jnp.int32)
        col = jnp.arange(P, dtype=jnp.int32)
        last = jnp.maximum(sl, 1) - 1
        pos = jnp.where(decode_rows[:, None],
                        jnp.minimum(col[None, :], last[:, None]),
                        jnp.broadcast_to(last[:, None], (n, P)))
        logits, cache, _, *load = model.forward_serve(
            params, {"tokens": toks}, cache, jnp.asarray(offs, jnp.int32),
            seq_lens=sl, pages=pages, decode_rows=decode_rows,
            logit_positions=pos, verify_len=P, moe_load=moe)  # (n, P, V)
        logits = jnp.where(poison[:, None, None], jnp.nan, logits)
        fin = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        drafts = toks[:, 1:P]                           # (n, P-1)
        valid = decode_rows[:, None] & (col[None, 1:] < sl[:, None])
        if temperature <= 0.0:
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # (n, P)
            match = (drafts == out[:, : P - 1]) & valid
            acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=-1),
                          axis=-1)
            return (cache, out, acc + 1, fin, *load)
        keys = _row_key_grid(base_key, rids, gens, P)   # (n, P) keys
        lt = _truncate_logits(logits.astype(jnp.float32) / temperature,
                              top_k, top_p)             # (n, P, V)
        p = jax.nn.softmax(lt, axis=-1)
        p_draft = jnp.take_along_axis(p[:, : P - 1], drafts[..., None],
                                      axis=-1)[..., 0]  # (n, P-1)
        u = jax.vmap(jax.vmap(
            lambda kk: jax.random.uniform(jax.random.fold_in(kk, 1))
        ))(keys[:, : P - 1])
        accept = valid & (u < p_draft)
        acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1),
                      axis=-1)                          # (n,) in [0, P-1]
        # the emission column: first rejected draft (resample from the
        # residual) or, when every draft survived, the bonus column
        l_acc = jnp.take_along_axis(lt, acc[:, None, None], axis=1)[:, 0]
        k_acc = jnp.take_along_axis(keys, acc[:, None, None], axis=1)[:, 0]
        d_acc = jnp.take_along_axis(
            toks[:, :P], jnp.minimum(acc + 1, P - 1)[:, None], axis=1)[:, 0]
        rejected = decode_rows & (acc < sl - 1)
        l_res = jnp.where(
            jax.nn.one_hot(d_acc, lt.shape[-1], dtype=bool), -jnp.inf, l_acc)
        t_rej = jax.vmap(
            lambda kk, ll: jax.random.categorical(jax.random.fold_in(kk, 2),
                                                  ll))(k_acc, l_res)
        t_bonus = jax.vmap(jax.random.categorical)(k_acc, l_acc)
        t = jnp.where(rejected, t_rej, t_bonus).astype(jnp.int32)
        shifted = jnp.concatenate(
            [drafts, jnp.zeros((n, 1), toks.dtype)], axis=1)  # (n, P)
        out = jnp.where(col[None, :] < acc[:, None], shifted, t[:, None])
        return (cache, out.astype(jnp.int32), acc + 1, fin, *load)

    return jax.jit(step, donate_argnums=(2,))


def plan_prefill_chunk(start: int, prompt_len: int, budget: int,
                       page_size: int = 0) -> int:
    """The end of the next admission-prefill chunk for a prompt at progress
    `start`: at most `budget` tokens, never past `prompt_len`, and — in
    paged mode — cut back to a page boundary whenever the chunk does not
    finish the prompt and a boundary past `start` is in reach (so decode
    and later chunks never write into a page a previous chunk left half
    validated mid-step).  Always advances (>= start + 1).  The final chunk
    ends exactly at `prompt_len`, which is what makes chunked admission
    compute every prompt token exactly once."""
    if not 0 <= start < prompt_len:
        raise ValueError(f"start {start} outside [0, {prompt_len})")
    if budget < 1:
        raise ValueError(f"prefill chunk budget must be >= 1, got {budget}")
    end = min(prompt_len, start + budget)
    if page_size and end < prompt_len:
        aligned = (end // page_size) * page_size
        if aligned > start:
            end = aligned
    return end


DEFER = object()
"""Sentinel: admission must wait for the wave in flight to publish its
prefix-directory entries (distinct from None == pool full)."""

# Host spans of one `Scheduler.step`, on the profiler's own clock (a no-op
# unless a trace is running): `sched.admit` (shedding, admission with,
# without mixed steps, its prefill wave, fault hooks), then per dispatch
# path `sched.plan` (page planning and the host arrays of the step),
# `sched.dispatch` (argument transfers and the jitted call; arguments name
# the program and its shape), `sched.readback` (reads of its outputs) and
# `sched.commit` (token bookkeeping and retirement), and a last
# `sched.commit` for the step's closing hooks.  A MoE stack's step also
# marks `moe.load` after its readback, with the load counts its program
# returned as arguments (`Scheduler._mark_load`).
_span = jax.profiler.TraceAnnotation


class Request:
    """One generation request tracked by the Scheduler.

    `deadline_ms` / `ttl_steps` are optional staleness bounds on the
    request's LIFETIME (from submit), enforced both at the queue and on
    admitted slots: a request older than `ttl_steps` scheduler steps —
    deterministic, what tests use — or `deadline_ms` wall-clock
    milliseconds (measured with the scheduler's injectable clock) is shed
    (queued) or retired mid-decode (admitted — partial tokens kept, pages
    freed) with `status == "deadline_missed"`.
    `status` is "queued" -> "done" | "deadline_missed" | "poisoned".
    """

    __slots__ = ("rid", "prompt", "max_new_tokens", "tokens", "done",
                 "deadline_ms", "ttl_steps", "submit_step", "submit_time",
                 "status", "spec_k")

    def __init__(self, rid: int, prompt: Sequence[int], max_new_tokens: int,
                 deadline_ms: Optional[float] = None,
                 ttl_steps: Optional[int] = None):
        self.rid = rid
        self.prompt = list(int(t) for t in prompt)
        self.max_new_tokens = int(max_new_tokens)
        self.tokens: List[int] = []
        self.done = False
        self.deadline_ms = None if deadline_ms is None else float(deadline_ms)
        self.ttl_steps = None if ttl_steps is None else int(ttl_steps)
        self.submit_step = 0
        self.submit_time = 0.0
        self.status = "queued"
        # adaptive speculative draft length; lives on the REQUEST (not the
        # slot) so it survives eviction + re-admission.  None until the
        # speculative scheduler lazily seeds it with its draft_len.
        self.spec_k: Optional[int] = None


class _SpillRecord:
    """Host-side victim-pool entry for one evicted slot: everything needed
    to rebuild the slot's page-table row bit-identically.

    `logical` is the slot's page list in LOGICAL order, each entry either
    ("host", i) — a formerly private page whose bytes live at index i of
    the fetched `data` tree (the device page was freed) — or ("ref", p) —
    a shared page that stayed resident because the prefix directory /
    other slots still hold it; the record itself keeps one refcount on p
    so no reclaim can free it before the restore.  `data` is the
    `device_get` of a `make_page_fetch_fn` gather padded to `width`
    (power of two) pages; `n_host` of them are real.  `covered` / `cur_tok`
    snapshot the slot's kv fill and pending decode input.  `crcs` are the
    spill-time per-host-page checksums (`integrity != "off"`; None
    otherwise) verified before any restore serves the bytes."""

    __slots__ = ("logical", "n_host", "width", "data", "covered", "cur_tok",
                 "crcs")

    def __init__(self, logical, n_host, width, data, covered, cur_tok,
                 crcs=None):
        self.logical = logical
        self.n_host = int(n_host)
        self.width = int(width)
        self.data = data
        self.covered = int(covered)
        self.cur_tok = int(cur_tok)
        self.crcs = crcs


LADDER_RUNGS = ("disable_speculation", "shrink_prefill_chunk",
                "pause_admission")
"""SLA degradation ladder, mildest first: each rung sheds speculative /
prefill / admission load in turn as pressure (queue depth p95, p95 time
between tokens vs target) persists, and is released in reverse order when
pressure clears.  Rungs change SCHEDULING only — never stream content."""


class Scheduler:
    """Continuous-batching request scheduler over a slot-based KV cache.

    The cache is `max_batch_slots` independent slots with per-slot lengths.
    `submit` queues requests; every `step`:

      1. admits queued requests into free slots — one bucketed ragged prefill
         + scatter-insert per admission wave,
      2. runs one fused `decode_chunk`-token scan over ALL slots (per-slot
         offsets/lengths; finished or empty slots cost zero kernel compute),
      3. retires slots whose sequence hit EOS / its token budget / capacity,
         freeing them for the next admission wave, and returns the newly
         generated (request_id, tokens) deltas for streaming.

    `run()` drives steps until every request completes and returns
    {request_id: generated tokens}.

    **Paged mode** (`page_size > 0`): KV memory is a shared pool of
    `num_pages` fixed-size pages instead of `max_batch_slots` dense
    `max_len` buffers; each slot holds a page-table row.  Admission is
    page-granular — a queued request is admitted whenever a free slot
    exists AND the free-page count covers its prompt (never a whole
    `max_len` slot), pages are allocated lazily as decode crosses page
    boundaries, and a retired request's pages return to the free list
    immediately.  When the pool is too fragmented to extend every active
    slot, the starved slots simply STALL for one chunk (their state is
    untouched; passing active=False makes them cost zero kernel compute);
    if no active slot can run at all, the most recently admitted one is
    evicted — its pages freed and the request re-queued as a continuation
    (prompt + tokens generated so far), which under greedy decoding resumes
    the exact same stream.

    **Prefix sharing** (`prefix_sharing=True`, paged mode only): every
    physical page carries a host-side refcount, and a **prefix directory**
    maps page-aligned token prefixes (plus exact full prompts) to the
    physical pages holding their KV.  Admission walks the directory and
    maps a request's leading page-table entries straight onto the matched
    pages (refcount++), skipping their prefill compute entirely — only the
    divergent tail (always >= 1 token, so the first sampled token has
    logits) runs through `make_paged_prefill_fn` at a per-row offset.  A
    write about to land in a page with refcount > 1 triggers copy-on-write
    (fresh page, device page copy, table-entry swap; the shared original is
    never touched).  Retirement decrements refcounts — only pages nobody
    holds return to the pool, so evict-youngest can never free a page
    another slot still reads — and additionally KEEPS the retiree's prompt
    pages in the directory keyed by prompt hash (retire -> keep), so later
    identical requests hit even after the original slot is gone.  Directory
    entries are LRU-evicted under pool pressure (and down to
    `prefix_cache_pages` distinct pages when that cap is set).

    **Mixed steps** (`mixed_steps=True`): admission no longer dispatches a
    monolithic prompt prefill.  An admitted request's slot enters a
    PREFILLING state (pages/prefix mapping/copy-on-write exactly as
    before), and while any slot is prefilling each scheduler step advances
    BOTH row classes: every decoding slot keeps decoding and the
    prefilling slots consume the next `plan_prefill_chunk` chunks of their
    prompts — `prefill_chunk_budget` prefill tokens per step, shared FCFS
    in admission order — so time between tokens is bounded by the chunk
    budget, never by another request's prompt length.

    The step's dispatch shape is `mixed_dispatch`:

      * ``"fused"`` (default) — ONE (B, L) mixed rectangle: decode rows
        contribute 1 token at column 0 and route through the very split-K
        launch an unchunked decode step uses, prefill rows through the
        ragged-Q prefill launch, inside the same program
        (`blocks._mixed_attend`; idle rows cost zero KV iterations via the
        q_len early-out).  One device dispatch per step — best when
        per-dispatch overhead is comparable to compute (small models, the
        CPU bench) and the only fused option for the dense slot cache
        (donated whole, so rows can't be sub-batched).
      * ``"paired"`` (paged mode only) — a chunk wave carrying ONLY the
        prefilling slots (any subset of page-table rows can dispatch
        against the shared pool) back-to-back with the regular decode
        chunk-scan.  The decode lane never pays the chunk rows' width
        through the row-batched linears/FFN — best when compute dominates
        dispatch overhead (large models on real hardware).

    A slot whose chunk completes its prompt samples its first token from
    that same dispatch; prefix-directory registration happens at
    completion (queued requests wanting a prefix still in flight wait,
    exactly like the unchunked DEFER).  Steps with no prefill in flight
    are plain decode chunk-scans — steady-state throughput is unchanged.
    Per-request outputs (and the quantized cache bytes behind them) are
    bit-identical to `mixed_steps=False`: chunked prefill writes the same
    per-token quantized KV, every row runs its unchunked kernel dispatch,
    and sampling keys are per-(request, token index).

    **Speculative decoding** (`speculate=True`): each step, every decoding
    slot's context (prompt + generated tokens) is scanned by the
    self-speculative n-gram proposer (`propose_draft_tokens`;
    `draft_mode="ngram"` — the seam where a small zoo draft model plugs in
    later) for up to `draft_len` draft tokens, and the decode row carries
    [current token, drafts...] as a q_len = 1 + k ragged verify row — ONE
    model pass scores every drafted position (multi-row split-K decode
    launch, bit-identical per position to the 1-token steps it replaces).
    The longest accepted prefix plus a bonus/correction token is emitted:
    up to `draft_len + 1` tokens per step per slot.  Greedy streams are
    bit-identical to the non-speculative scheduler; temperature > 0 uses
    distribution-preserving rejection sampling on the per-(request,
    token-index) key stream, so runs stay seed-deterministic.  Rejected
    drafts' KV is written but never advertised (the ragged-length
    contract IS the rollback); the page allocator pre-extends each row
    for its k + 1 writes (CoW/prefix/spill-aware), shrinking a starved
    row's draft to 0 before falling back to eviction.  A per-request
    adaptive k (`Request.spec_k`) grows on fully-accepted steps and
    halves on fully-rejected ones, so slots that stop repeating
    themselves degrade gracefully to ~plain decode.

    **Crash recovery** (`snapshot()` / `restore()`): `snapshot()` writes
    the ENTIRE serving state — KV pool bytes, every request (queue order,
    slot assignments, partial streams), page tables/refcounts, prefix
    directory, victim pool, sampling key, fault-injection rng — through
    the atomic+checksummed `repro.checkpoint` machinery; `restore()` on a
    same-config scheduler resumes mid-trace with BIT-IDENTICAL
    continuation streams (greedy and sampled, dense+paged, sharing /
    speculation / mixed steps on), because sampling keys are
    per-(request, token index) and every scheduling input (free-list
    order, admission stamps, LRU order) round-trips exactly.
    `snapshot_every` + `snapshot_dir` auto-snapshot at a step cadence;
    `FaultPlan(crash_at_step=s)` raises `CrashInjected` at step s to
    exercise the recovery path deterministically.

    **KV-page integrity** (`integrity="checksum"|"paranoid"`, paged mode):
    per-page crc32 checksums are recorded the moment pages become
    immutable — prefix-directory registration (copy-on-write keeps shared
    pages frozen) and victim-pool spill — and verified whenever those
    bytes come back to serve: victim restore, and snapshot `restore()`
    (directory pages are re-checksummed against their write-time crcs).
    A mismatch increments `stats["corruptions_detected"]` and RECOVERS
    instead of serving corrupt bytes: a bad spill record is dropped and
    the continuation re-prefilled from its prompt (bit-identical stream);
    a bad directory page quarantines every prefix entry holding it —
    quarantined keys can never re-enter the directory (`audit()`
    asserts).  `"paranoid"` additionally verifies directory pages at
    every lookup hit and LRU eviction, and the victim pool inside
    `audit()` (so `REPRO_AUDIT=1` sweeps every record every step).

    **Degradation ladder** (`tbt_target_ms > 0`): a pressure signal —
    queue-depth p95 over the last 32 steps vs `queue_depth_target`
    (default 2*slots) OR p95 time-between-tokens vs `tbt_target_ms` —
    climbs `LADDER_RUNGS` one rung per `ladder_cooldown_steps`:
    disable_speculation -> shrink_prefill_chunk (budget halved) ->
    pause_admission (new admissions wait; a fully idle scheduler still
    admits, so the ladder can never livelock), and steps back down as
    pressure clears.  Every transition is counted in `stats`
    (`ladder_transitions` per rung, escalations/deescalations totals).
    Rungs change scheduling only, so streams stay bit-identical.
    """

    def __init__(self, model: Model, params, *, max_batch_slots: int = 8,
                 max_len: int = 2048, eos_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0,
                 decode_chunk: int = 8, rng: Optional[jax.Array] = None,
                 prefill_bucket: int = 16,
                 page_size: int = 0, num_pages: int = 0,
                 prefix_sharing: bool = False, prefix_cache_pages: int = 0,
                 mixed_steps: bool = False, prefill_chunk_budget: int = 0,
                 mixed_dispatch: str = "fused",
                 victim_pool_pages: int = 0, max_queue: int = 0,
                 speculate: bool = False, draft_len: int = 4,
                 draft_mode: str = "ngram",
                 fault_plan: Optional[FaultPlan] = None,
                 audit_every_step: Optional[bool] = None,
                 kv_bits: int = 0,
                 integrity: str = "off",
                 tbt_target_ms: float = 0.0,
                 queue_depth_target: int = 0,
                 ladder_cooldown_steps: int = 8,
                 snapshot_every: int = 0,
                 snapshot_dir: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic):
        if kv_bits and kv_bits != model.cfg.kv_bits:
            # rebuild the step closures around the requested KV precision —
            # cache layout is baked into every jitted step, so a config
            # override (not a runtime flag) is the only correct seam
            model = build_model(
                dataclasses.replace(model.cfg, kv_bits=int(kv_bits)))
        if not scheduler_supported(model.cfg):
            raise NotImplementedError(
                f"arch {model.cfg.name!r} is not supported by the slot "
                "scheduler (needs a pure attention stack, no windows, no "
                "encoder-decoder)")
        self.model = model
        self.params = params
        self._moe = _moe_stack(model)
        self.B = int(max_batch_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.decode_chunk = int(decode_chunk)
        self.prefill_bucket = int(prefill_bucket)
        self.key = jax.random.PRNGKey(0) if rng is None else rng

        self.speculate = bool(speculate)
        self.draft_len = int(draft_len)
        self.draft_mode = str(draft_mode)
        if self.speculate:
            if self.draft_len < 1:
                raise ValueError(
                    f"draft_len must be >= 1, got {draft_len}")
            if self.draft_mode != "ngram":
                raise ValueError(
                    f"unknown draft_mode {draft_mode!r} (only the "
                    "self-speculative 'ngram' proposer exists today; a "
                    "zoo draft model plugs in here later)")
        self.mixed_steps = bool(mixed_steps)
        self.prefill_chunk_budget = int(prefill_chunk_budget) or 32
        if self.mixed_steps and self.prefill_chunk_budget < 1:
            raise ValueError("prefill_chunk_budget must be >= 1")
        if mixed_dispatch not in ("fused", "paired"):
            raise ValueError(f"unknown mixed_dispatch {mixed_dispatch!r}")
        if mixed_dispatch == "paired" and not int(page_size) > 0:
            raise ValueError("mixed_dispatch='paired' requires page_size > 0 "
                             "(only page-table rows can be sub-batched)")
        self.mixed_dispatch = mixed_dispatch
        # admission stamps order chunk scheduling (FCFS) and break eviction
        # ties; maintained in both dense and paged modes
        self._admit_seq = np.zeros(self.B, np.int64)
        self._admit_counter = 0
        # mixed-step prefilling state: a slot mid-chunked-prefill holds its
        # full pending token list; `lengths` doubles as its progress cursor
        self.prefilling = np.zeros(self.B, bool)
        self._pend: List[Optional[List[int]]] = [None] * self.B
        # slot -> prefix keys it will register at completion (mixed mode):
        # queued requests wanting any of them DEFER until then
        self._inflight_keys: Dict[int, set] = {}
        self.paged = int(page_size) > 0
        if self.paged:
            self.page_size = int(page_size)
            self.max_pages = self._pages_for(self.max_len)
            # default pool: as many tokens as the dense slot cache would pin
            # (+ the reserved trash page) — callers shrink num_pages to
            # overcommit slots against a smaller KV budget
            self.num_pages = int(num_pages) or self.B * self.max_pages + 1
            if self.num_pages - 1 < self.max_pages:
                raise ValueError(
                    f"num_pages={self.num_pages} cannot hold one full-length "
                    f"sequence ({self.max_pages} pages + 1 reserved)")
            self.free_pages: List[int] = list(range(1, self.num_pages))
            self.page_table = np.full((self.B, self.max_pages), -1, np.int32)
            self.peak_pages_in_use = 0
            # per-page refcount: holders are slot table rows + directory
            # entries; only pages that drop to 0 return to the free list
            self.page_ref = np.zeros(self.num_pages, np.int32)
            self.cache = model.init_cache(
                self.B, self.max_len, ragged=True,
                page_size=self.page_size, num_pages=self.num_pages)
        else:
            self.cache = model.init_cache(self.B, self.max_len, ragged=True)
        self.prefix_sharing = bool(prefix_sharing)
        if self.prefix_sharing and not self.paged:
            raise ValueError("prefix_sharing requires page_size > 0")
        self.prefix_cache_pages = int(prefix_cache_pages)
        # prefix directory: serialized token prefix -> (pages, tokens
        # covered); insertion order == LRU order (move_to_end on hit)
        self.prefix_dir: "collections.OrderedDict[bytes, Tuple[Tuple[int, ...], int]]" = \
            collections.OrderedDict()
        self._dir_ref: Dict[int, int] = {}    # page -> directory refcount
        self._last_keys: list = []            # per-candidate key scratch
        self.prefix_hits = 0                  # admissions that mapped pages
        self.prefix_hit_tokens = 0            # prefill tokens skipped
        self.prefill_tokens_computed = 0      # prefill tokens actually run
        self.n_cow_copies = 0                 # copy-on-write page copies
        self.prefix_evictions = 0             # directory entries LRU-evicted
        self.lengths = np.zeros(self.B, np.int32)     # per-slot kv fill
        self.active = np.zeros(self.B, bool)
        self.remaining = np.zeros(self.B, np.int32)   # token budget left
        self.cur_tok = np.full(self.B, -1, np.int32)  # next decode input
        self.slot_req: List[Optional[Request]] = [None] * self.B
        self.queue: "collections.deque[Request]" = collections.deque()
        self._next_rid = 0

        # -- overload control: victim pool, bounded queue, deadlines -------
        self.victim_pool_pages = int(victim_pool_pages)
        if self.victim_pool_pages and not self.paged:
            raise ValueError("victim_pool_pages requires page_size > 0 "
                             "(only paged KV can spill page-granularly)")
        self.max_queue = int(max_queue)
        self._clock = clock
        self._faults = fault_plan.start() if fault_plan is not None else None
        if audit_every_step is None:
            audit_every_step = bool(int(os.environ.get("REPRO_AUDIT", "0")))
        self._audit_every = bool(audit_every_step)
        # rid -> _SpillRecord for evicted-but-spilled continuations; the
        # request itself sits in the queue like any eviction continuation,
        # and admission restores instead of re-prefilling when a record
        # exists
        self._victim: Dict[int, _SpillRecord] = {}
        self._victim_used = 0                 # host pages currently held
        if self.paged:
            # per-token byte width follows the cache's STORED precision
            # (kv_bits=4 packs two codes per byte), so spill accounting and
            # capacity planning both halve with the cache
            self._page_bytes = self.page_size * kv_bytes_per_token(model.cfg)
        else:
            self._page_bytes = 0
        self._step_idx = 0
        self._queue_depths: List[int] = []
        # dense-mode evictions exist too (forced by fault injection), so the
        # counter lives here, shared by both storage modes
        self.n_evictions = 0
        self.n_spills = 0                     # evictions spilled to host
        self.n_restores = 0                   # spilled slots re-admitted
        self.spilled_pages = 0                # device->host pages moved
        self.spill_bytes = 0                  # analytic bytes spilled
        self.n_recompute_fallbacks = 0        # spills refused (pool cap)
        self.n_deadline_misses = 0            # queued requests shed stale
        self.n_rejections = 0                 # submits bounced (Overloaded)
        self.n_reclaim_stalls = 0             # reclaim gave up: dir pinned
        self.refcount_corruptions_detected = 0
        # speculation accounting + the tokens-per-model-step denominator
        # (one unit per device forward: a decode chunk-scan counts its
        # chunk length, every other dispatch counts 1)
        self.model_steps = 0
        self.n_spec_steps = 0                 # speculative dispatches run
        self.spec_proposed = 0                # draft tokens sent to verify
        self.spec_accepted = 0                # draft tokens accepted
        self.spec_rejected = 0                # draft tokens rejected

        # -- integrity: write/spill-time page checksums + quarantine -------
        if integrity not in ("off", "checksum", "paranoid"):
            raise ValueError(f"unknown integrity mode {integrity!r} "
                             "(off | checksum | paranoid)")
        if integrity != "off" and not self.paged:
            raise ValueError("integrity checksums are page-granular — "
                             "they require page_size > 0")
        self.integrity = str(integrity)
        # physical page -> crc32 at registration time; keys are always a
        # subset of the directory-held pages (recorded at _dir_put, dropped
        # when the last directory hold goes) — slot-private pages are
        # mutable and never checksummed
        self.page_crc: Dict[int, int] = {}
        self.quarantined: set = set()         # prefix keys barred for good
        self.corruptions_detected = 0
        self.bitflips_injected = 0
        self.n_poisoned = 0
        self._poison_mask = np.zeros(self.B, bool)

        # -- SLA degradation ladder ----------------------------------------
        self.tbt_target_ms = float(tbt_target_ms)
        self.queue_depth_target = int(queue_depth_target) or 2 * self.B
        self.ladder_cooldown_steps = max(1, int(ladder_cooldown_steps))
        self.ladder_level = 0
        self.ladder_escalations = 0
        self.ladder_deescalations = 0
        self.ladder_paused_steps = 0
        self.ladder_transitions = {r: 0 for r in LADDER_RUNGS}
        self._ladder_last_change = 0
        self._tbt_samples: "collections.deque[float]" = \
            collections.deque(maxlen=32)
        self._last_step_time: Optional[float] = None

        # -- snapshot/restore ----------------------------------------------
        self.snapshot_every = int(snapshot_every)
        self.snapshot_dir = snapshot_dir
        if self.snapshot_every and not self.snapshot_dir:
            raise ValueError("snapshot_every requires snapshot_dir")
        self.n_snapshots = 0
        # every request ever submitted, by rid — what snapshot() captures
        # and results() reads; queue/slots reference these same objects
        self.requests: Dict[int, Request] = {}

    # -- request intake -----------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               deadline_ms: Optional[float] = None,
               ttl_steps: Optional[int] = None) -> int:
        """Queue a request.  Raises a typed `SubmitError` subclass for
        requests that can never be served (`EmptyPrompt`, `InvalidBudget`,
        `PromptTooLong` — an unchecked over-long prompt would wedge FCFS
        admission forever) and `Overloaded` when the bounded queue
        (`max_queue`) is full — backpressure, not failure; the caller
        sheds load or retries."""
        prompt = list(prompt)
        if len(prompt) == 0:
            raise EmptyPrompt("empty prompt: nothing to condition on")
        if int(max_new_tokens) <= 0:
            raise InvalidBudget(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) >= self.max_len:
            raise PromptTooLong(
                f"prompt length {len(prompt)} >= max_len {self.max_len} "
                "(no room for even one generated token)")
        if self.paged and self._pages_for(len(prompt) + 1) > self.num_pages - 1:
            # defense in depth: with the init-time pool floor this cannot
            # fire today, but a relaxed pool must never wedge admission
            raise PromptTooLong(
                f"prompt needs {self._pages_for(len(prompt) + 1)} pages; the "
                f"pool only has {self.num_pages - 1}")
        if self.max_queue and len(self.queue) >= self.max_queue:
            self.n_rejections += 1
            raise Overloaded(
                f"admission queue full ({self.max_queue} requests)")
        r = Request(self._next_rid, prompt, max_new_tokens,
                    deadline_ms=deadline_ms, ttl_steps=ttl_steps)
        r.submit_step = self._step_idx
        r.submit_time = self._clock()
        self._next_rid += 1
        self.requests[r.rid] = r
        self.queue.append(r)
        return r.rid

    def _is_stale(self, r: Request) -> bool:
        if (r.ttl_steps is not None
                and self._step_idx - r.submit_step > r.ttl_steps):
            return True
        if (r.deadline_ms is not None
                and (self._clock() - r.submit_time) * 1e3 > r.deadline_ms):
            return True
        return False

    def _shed_stale(self):
        """Drop queued requests past their deadline/ttl (a stale request
        would only steal capacity from ones that can still make it).  A
        shed spilled continuation also releases its victim-pool record."""
        if not self.queue:
            return
        kept: "collections.deque[Request]" = collections.deque()
        while self.queue:
            r = self.queue.popleft()
            if self._is_stale(r):
                r.done = True
                r.status = "deadline_missed"
                self.n_deadline_misses += 1
                self._drop_victim(r.rid)
            else:
                kept.append(r)
        self.queue = kept

    def _shed_admitted(self):
        """Deadline/ttl enforcement for ADMITTED requests: a running (or
        mid-chunked-prefill) slot whose request's LIFETIME bound expired is
        retired with `status="deadline_missed"` — partial tokens kept on
        the request, pages freed immediately (no prefix registration: a
        prefilling slot's prompt KV may be incomplete, and an SLA miss is
        not worth pinning pages for).  Without this, one slow resident
        could hold a slot arbitrarily past its SLA while queued requests
        that could still make their deadlines starve behind it."""
        for b in range(self.B):
            r = self.slot_req[b]
            if r is not None and self._is_stale(r):
                self.n_deadline_misses += 1
                self._retire(b, status="deadline_missed", register=False)

    # -- scheduling ---------------------------------------------------------
    def _bucket(self, n: int) -> int:
        b = self.prefill_bucket
        while b < n:
            b *= 2
        # never compile a prefill wider than the cache: positions past
        # max_len-1 could only ever hold clipped, masked garbage
        return min(b, self.max_len)

    # -- page allocator (paged mode; host-side, pages are device-opaque) ----
    def _pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page_size)

    def _alloc_slot(self, slot: int, tokens: int) -> bool:
        """Grow `slot`'s page-table row to cover `tokens` tokens
        (all-or-nothing; already-covered prefixes — including prefix-shared
        mappings — are free).  Under prefix sharing a shortage first
        reclaims LRU directory entries before reporting failure."""
        need = self._pages_for(min(int(tokens), self.max_len))
        row = self.page_table[slot]
        have = int((row >= 0).sum())
        if need <= have:
            return True
        # fault injection: report this (real) allocation as failed —
        # queried only when pages would actually be taken, so no-op calls
        # never advance the plan's rng stream
        if self._faults is not None and self._faults.fail_alloc(self._step_idx):
            return False
        if need - have > len(self.free_pages):
            self._reclaim(need - have)
            if need - have > len(self.free_pages):
                return False
        for j in range(have, need):
            p = self.free_pages.pop()
            self.page_ref[p] = 1
            row[j] = p
        return True

    def _free_slot_pages(self, slot: int):
        """Drop the slot's hold on its pages; only pages with no remaining
        holder (no other slot, no directory entry) return to the pool."""
        row = self.page_table[slot]
        for p in row[row >= 0]:
            p = int(p)
            self.page_ref[p] -= 1
            if self.page_ref[p] == 0:
                self.free_pages.append(p)
        row[:] = -1

    def pages_in_use(self) -> int:
        """Allocated (non-free, non-trash) pages right now (paged mode) —
        shared pages count ONCE, which is the whole point of sharing."""
        return (self.num_pages - 1) - len(self.free_pages)

    # -- prefix directory (prefix sharing; host-side metadata) --------------
    @staticmethod
    def _prefix_key(tokens: Sequence[int]) -> bytes:
        return np.asarray(tokens, np.int32).tobytes()

    def directory_pages(self) -> int:
        """Distinct physical pages currently pinned by directory entries."""
        return len(self._dir_ref)

    def _dir_put(self, key: bytes, pages: Sequence[int], covered: int):
        if key in self.quarantined:
            # a checksum mismatch poisoned this prefix for good: it must
            # never re-enter the directory (audit asserts), so later
            # identical prompts always recompute fresh bytes
            return
        if key in self.prefix_dir:
            self.prefix_dir.move_to_end(key)
            return
        # the pages become immutable the moment the directory holds them
        # (copy-on-write privatizes any future write) — record their
        # write-time checksums now, the reference every later verify
        # (restore / paranoid hit / paranoid eviction) compares against
        self._record_page_crcs(pages)
        for p in pages:
            self.page_ref[p] += 1
            self._dir_ref[p] = self._dir_ref.get(p, 0) + 1
        self.prefix_dir[key] = (tuple(int(p) for p in pages), int(covered))
        if self.prefix_cache_pages:
            while (len(self._dir_ref) > self.prefix_cache_pages
                   and self.prefix_dir):
                self._dir_evict_one()

    def _dir_evict_one(self, key: Optional[bytes] = None, verify=True):
        if key is None:
            key, (pages, _) = self.prefix_dir.popitem(last=False)   # LRU
        else:
            pages, _ = self.prefix_dir.pop(key)
        if verify and self.integrity == "paranoid":
            bad = self._verify_pages(pages)
            if bad:
                self.corruptions_detected += bad
                self.quarantined.add(key)
        for p in pages:
            self.page_ref[p] -= 1
            self._dir_ref[p] -= 1
            if self._dir_ref[p] == 0:
                del self._dir_ref[p]
                self.page_crc.pop(p, None)
            if self.page_ref[p] == 0:
                self.free_pages.append(p)
        self.prefix_evictions += 1

    def _quarantine_entry(self, key: bytes):
        """Bar `key` from the directory for good (and evict its live entry
        if present) — the detect half of detect-and-recompute: later
        prompts matching this prefix recompute their KV from scratch."""
        self.quarantined.add(key)
        if key in self.prefix_dir:
            self._dir_evict_one(key, verify=False)

    # -- page checksums (integrity != "off"; host-side crc32) ---------------
    def _compute_page_crcs(self, pages: Sequence[int]) -> List[int]:
        """Current crc32 of each listed physical page's pool bytes across
        every layer (one power-of-two-padded fetch + host checksum)."""
        width = 1
        while width < len(pages):
            width *= 2
        padded = list(pages) + [TRASH_PAGE] * (width - len(pages))
        data = jax.device_get(make_page_fetch_fn(self.model)(
            self.cache, jnp.asarray(padded, jnp.int32)))
        return [int(c) for c in
                T.cache_page_checksums(data, list(range(len(pages))))]

    def _record_page_crcs(self, pages: Sequence[int]):
        if self.integrity == "off":
            return
        new = [int(p) for p in pages if int(p) not in self.page_crc]
        if not new:
            return
        for p, c in zip(new, self._compute_page_crcs(new)):
            self.page_crc[p] = c

    def _verify_pages(self, pages: Sequence[int]) -> int:
        """Number of listed pages whose CURRENT pool bytes no longer match
        their write-time checksum (pages without a recorded crc — never
        directory-registered — are skipped: they are mutable by design)."""
        if self.integrity == "off":
            return 0
        known = [int(p) for p in pages if int(p) in self.page_crc]
        if not known:
            return 0
        crcs = self._compute_page_crcs(known)
        return sum(1 for p, c in zip(known, crcs) if c != self.page_crc[p])

    def _verify_victim(self, rec: _SpillRecord) -> bool:
        """Re-checksum a spill record's host pages against its spill-time
        crcs; counts mismatches in `corruptions_detected`.  False means
        the bytes must NOT be restored (recompute-from-prompt instead)."""
        if self.integrity == "off" or rec.crcs is None or not rec.n_host:
            return True
        crcs = T.cache_page_checksums(rec.data, list(range(rec.n_host)))
        bad = sum(1 for a, b in zip(crcs, rec.crcs) if int(a) != int(b))
        if bad:
            self.corruptions_detected += bad
        return bad == 0

    def _reclaim(self, need: int):
        """LRU-evict directory entries until `need` pages are free (pages a
        live slot still holds survive eviction — only the directory's hold
        is dropped).  Only entries whose eviction actually FREES a page are
        considered (a page frees iff the directory hold is its last
        refcount): under pressure the directory may hold only prefixes
        whose pages live slots / the victim pool still pin — evicting
        those frees nothing, so reclaim must break with a stall stat
        instead of spinning through (and churning) the whole directory."""
        while len(self.free_pages) < need:
            victim = None
            for key, (pages, _) in self.prefix_dir.items():   # LRU order
                if any(self.page_ref[p] == 1 for p in pages):
                    victim = key
                    break
            if victim is None:
                if self.prefix_dir:
                    self.n_reclaim_stalls += 1
                break
            self._dir_evict_one(victim)

    def clear_prefix_cache(self):
        """Drop every directory entry (refcounts released; pages no slot
        holds return to the pool)."""
        while self.prefix_dir:
            self._dir_evict_one()

    def _lookup_prefix(self, prompt: Sequence[int]):
        """Longest directory match for `prompt`: the exact full prompt
        first (retire->keep entries cover the partial last page too), then
        page-aligned prefixes longest-first.  Returns (pages, covered) or
        (None, 0).  Matched entries move to MRU.  `integrity="paranoid"`
        re-checksums a hit's pages BEFORE mapping them: a corrupt hit is
        quarantined (never served) and the walk falls through to shorter
        prefixes / a full recompute."""
        buf = self._prefix_key(prompt)
        hit = self.prefix_dir.get(buf)
        if hit is not None and hit[1] == len(prompt):
            if self._paranoid_hit_bad(buf, hit):
                hit = None
            else:
                self.prefix_dir.move_to_end(buf)
                return hit
        for k in range(len(prompt) // self.page_size, 0, -1):
            key = buf[: 4 * k * self.page_size]
            hit = self.prefix_dir.get(key)
            if hit is not None and hit[1] == k * self.page_size:
                if self._paranoid_hit_bad(key, hit):
                    continue
                self.prefix_dir.move_to_end(key)
                return hit
        return None, 0

    def _paranoid_hit_bad(self, key: bytes, hit) -> bool:
        if self.integrity != "paranoid":
            return False
        bad = self._verify_pages(hit[0])
        if bad:
            self.corruptions_detected += bad
            self._quarantine_entry(key)
        return bad > 0

    def _registration_keys(self, prompt: Sequence[int], exact: bool):
        """The directory keys `_register_prefixes` would insert for this
        prompt (used both for registration and for the intra-wave pending
        check).  The prompt is serialized ONCE and sliced — int32 keys are
        4 bytes/token, so prefix k's key is the first 4*k*ps bytes."""
        ps = self.page_size
        buf = self._prefix_key(prompt)
        keys = [(buf[: 4 * k * ps], k, k * ps)
                for k in range(1, len(prompt) // ps + 1)]
        if exact and len(prompt) % ps:
            keys.append((buf, self._pages_for(len(prompt)), len(prompt)))
        return keys

    def _register_prefixes(self, slot: int, prompt: Sequence[int],
                           exact: bool):
        """Publish `slot`'s freshly valid prompt KV: one entry per
        page-aligned prefix (and, with `exact`, the full prompt including
        its partial last page — the retire->keep entry).  MUST be called
        only when no further write can land in the covered pages: after
        the admission prefill for aligned prefixes (decode writes start
        past the last full prompt page), at retirement for the exact
        entry."""
        row = self.page_table[slot]
        for key, n_pages, covered in self._registration_keys(prompt, exact):
            self._dir_put(key, [int(p) for p in row[:n_pages]], covered)

    # -- copy-on-write ------------------------------------------------------
    def _cow_range(self, slot: int, start: int, end: int,
                   pairs: List[Tuple[int, int]]) -> bool:
        """Privatize `slot`'s pages overlapping write range [start, end):
        any allocated page there with refcount > 1 gets a fresh page
        (appended to `pairs` as a (src, dst) device copy) and the table
        entry swapped.  Returns False if a fresh page cannot be found even
        after reclaiming directory entries (already-swapped entries stay
        swapped; their copies must still be applied)."""
        if start >= end:
            return True
        row = self.page_table[slot]
        ps = self.page_size
        for j in range(start // ps, (end - 1) // ps + 1):
            p = int(row[j])
            if p < 0 or self.page_ref[p] <= 1:
                continue
            if not self.free_pages:
                self._reclaim(1)
                if not self.free_pages:
                    return False
            fresh = self.free_pages.pop()
            self.page_ref[fresh] = 1
            self.page_ref[p] -= 1        # shared original: never reaches 0
            row[j] = fresh
            pairs.append((p, fresh))
            self.n_cow_copies += 1
        return True

    def _apply_copies(self, pairs: List[Tuple[int, int]]):
        """Run the collected CoW page copies as ONE device dispatch (before
        the wave's prefill/decode, which reads the private copies).  The
        pair count is padded to the next power of two with trash->trash
        no-op copies so the jitted copy program compiles O(log n) shapes,
        not one per distinct CoW count."""
        if not pairs:
            return
        n = 1
        while n < len(pairs):
            n *= 2
        pad = [(TRASH_PAGE, TRASH_PAGE)] * (n - len(pairs))
        src = jnp.asarray([s for s, _ in pairs + pad], jnp.int32)
        dst = jnp.asarray([d for _, d in pairs + pad], jnp.int32)
        self.cache = make_page_copy_fn(self.model)(self.cache, src, dst)

    def _eviction_victim(self) -> int:
        """The youngest active slot.  Ties on admission sequence (e.g. a
        state restored from a snapshot, or future batched admission stamps)
        break on the HIGHEST request id — a property of the request, not of
        slot-index/dict iteration order, so eviction is deterministic
        across runs and hosts."""
        slots = np.flatnonzero(self.active)
        return int(max(slots, key=lambda b: (int(self._admit_seq[b]),
                                             self.slot_req[b].rid)))

    def _evict(self, slot: int):
        """Evict a starved slot and re-queue its request as a continuation.

        With a victim pool (`victim_pool_pages > 0`) the slot's KV is
        SPILLED first — private pages copied device->host, shared pages
        kept resident under a victim-pool refcount — so re-admission is an
        O(pages) restore instead of an O(prompt + tokens) re-prefill.
        Without a pool (or when its cap is hit) the classic recompute
        continuation runs: pages freed, prompt + tokens re-prefilled on
        re-admission — identical output either way, because sampling keys
        are per-(request, token index), not a serially split stream.
        Pages other holders (slots sharing the prefix, directory entries)
        still reference merely lose this slot's refcount; never freed."""
        r = self.slot_req[slot]
        spilled = False
        if (r is not None and self.victim_pool_pages
                and not self.prefilling[slot] and self.lengths[slot] > 0):
            spilled = self._spill(slot, r)
        self.slot_req[slot] = None
        self.active[slot] = False
        self.lengths[slot] = 0
        self.cur_tok[slot] = -1
        self.prefilling[slot] = False
        self._pend[slot] = None
        self._poison_mask[slot] = False
        self._inflight_keys.pop(slot, None)
        if self.paged and not spilled:
            self._free_slot_pages(slot)
        self.n_evictions += 1
        if r is not None:
            self.queue.appendleft(r)

    def _spill(self, slot: int, r: Request) -> bool:
        """Move `slot`'s KV into the host victim pool (hierarchical spill).

        Private pages (refcount 1 — this slot is the only holder) are
        fetched device->host in ONE power-of-two-padded gather, then freed
        on device; shared pages (prefix-directory / other-slot holders)
        stay resident — the record takes over this slot's refcount on
        them, so the bytes survive any reclaim until the restore.  Returns
        False (recompute fallback) when the pool cap cannot take the
        private pages."""
        row = self.page_table[slot]
        alloc = [int(p) for p in row[row >= 0]]
        private = [p for p in alloc if self.page_ref[p] == 1]
        n = len(private)
        if self._victim_used + n > self.victim_pool_pages:
            self.n_recompute_fallbacks += 1
            return False
        width = 1
        while width < max(n, 1):
            width *= 2
        data = None
        crcs = None
        if n:
            padded = private + [TRASH_PAGE] * (width - n)
            data = jax.device_get(make_page_fetch_fn(self.model)(
                self.cache, jnp.asarray(padded, jnp.int32)))
            if self.integrity != "off":
                # spill-time checksums over the HOST copy (positional index
                # into the fetched tree) — verified before any restore maps
                # these bytes back into the pool
                crcs = tuple(int(c) for c in T.cache_page_checksums(
                    data, list(range(n))))
        host_idx = {p: i for i, p in enumerate(private)}
        logical: List[Tuple[str, int]] = []
        for p in alloc:
            if self.page_ref[p] == 1:
                logical.append(("host", host_idx[p]))
                self.page_ref[p] = 0
                self.free_pages.append(p)
            else:
                # the record REPLACES the slot as this page's holder: the
                # slot's hold is dropped and the victim hold added in one
                # move, so the net refcount is unchanged
                logical.append(("ref", p))
        row[:] = -1
        self._victim[r.rid] = _SpillRecord(
            logical, n, width, data,
            int(self.lengths[slot]), int(self.cur_tok[slot]), crcs)
        self._victim_used += n
        self.n_spills += 1
        self.spilled_pages += n
        self.spill_bytes += n * self._page_bytes
        return True

    def _restore(self, slot: int, r: Request, rec: _SpillRecord) -> bool:
        """Re-admit a spilled continuation: scatter its host pages into
        freshly allocated physical pages (one power-of-two-padded device
        write mirroring the fetch), re-map the shared entries (the victim
        hold transfers back to the slot), rebuild the page-table row in
        logical order and resume DECODING exactly where eviction stopped —
        no prefill, bit-identical to a never-evicted slot because whole
        already-quantized pages round-tripped untouched.  Returns False
        when the pool cannot supply the fresh pages yet (the continuation
        stays at the queue head — FCFS)."""
        n = rec.n_host
        if n > len(self.free_pages):
            self._reclaim(n)
            if n > len(self.free_pages):
                return False
        fresh = [self.free_pages.pop() for _ in range(n)]
        for p in fresh:
            self.page_ref[p] = 1
        row = self.page_table[slot]
        for j, (kind, val) in enumerate(rec.logical):
            row[j] = fresh[val] if kind == "host" else val
        if n:
            dst = fresh + [TRASH_PAGE] * (rec.width - n)
            self.cache = make_page_restore_fn(self.model)(
                self.cache, jnp.asarray(dst, jnp.int32), rec.data)
        del self._victim[r.rid]
        self._victim_used -= n
        self.slot_req[slot] = r
        self.lengths[slot] = rec.covered
        self.cur_tok[slot] = rec.cur_tok
        self.remaining[slot] = r.max_new_tokens - len(r.tokens)
        self.active[slot] = True
        self.prefilling[slot] = False
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        self.n_restores += 1
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use())
        return True

    def _drop_victim(self, rid: int):
        """Release a victim-pool record without restoring it (the request
        was shed): host pages are simply forgotten, and the record's holds
        on still-resident shared pages are dropped (freeing any page
        nobody else holds)."""
        rec = self._victim.pop(rid, None)
        if rec is None:
            return
        self._victim_used -= rec.n_host
        for kind, p in rec.logical:
            if kind == "ref":
                self.page_ref[p] -= 1
                if self.page_ref[p] == 0:
                    self.free_pages.append(p)

    def _split_load(self, out):
        """A step program's outputs, and its MoE load counts (the last
        output of a MoE stack's program; None otherwise)."""
        if self._moe:
            return out[:-1], out[-1]
        return out, None

    @staticmethod
    def _mark_load(load):
        """The `moe.load` span: the step's routed assignments that reached
        held experts and its (forward, layer, held expert) triples that
        received any, read back with its tokens."""
        if load is not None:
            load = np.asarray(load)
            with _span("moe.load", assignments=int(load[0]),
                       active=int(load[1])):
                pass

    def _retire(self, slot: int, status: str = "done",
                register: bool = True):
        """Vacate `slot`.  `status` lands on the request (`"done"` for a
        normal completion; `"deadline_missed"` / `"poisoned"` for forced
        retirement — partial tokens are KEPT, pages freed).  `register`
        gates prefix publication: a poisoned request's KV pages must never
        enter the directory."""
        r = self.slot_req[slot]
        if r is not None:
            r.done = True
            r.status = status
        self.slot_req[slot] = None
        self.active[slot] = False
        self.lengths[slot] = 0
        self.prefilling[slot] = False
        self._pend[slot] = None
        self._poison_mask[slot] = False
        self._inflight_keys.pop(slot, None)
        if self.paged:
            if self.prefix_sharing and r is not None and register:
                # retire -> keep: publish the full prompt's pages (incl.
                # the partial last page — its prompt rows are valid; rows
                # beyond are this request's decode garbage, never
                # advertised because a later hit re-runs the last prompt
                # token through CoW) before dropping the slot's hold
                self._register_prefixes(slot, r.prompt, exact=True)
            self._free_slot_pages(slot)

    def _try_admit_paged(self, slot: int, r: Request, pending_keys,
                         cow_pairs: List[Tuple[int, int]]) -> Optional[int]:
        """Place request `r` into `slot` (paged mode): prefix-directory
        mapping (when sharing), copy-on-write for the tail write range, and
        fresh-page allocation for the rest.  Returns the tail offset
        (prompt tokens whose prefill is skipped; 0 without a directory
        hit), None when the pool cannot hold the request, or DEFER when
        the request must wait for the wave in flight to publish a matching
        prefix (admitting now would duplicate the pages it is about to
        register — the follow-up wave in the same `_admit` call maps them
        instead)."""
        pend = r.prompt + r.tokens
        p_len = len(pend)
        if self.prefix_sharing:
            keys = self._registration_keys(pend, True)
            if any(key in pending_keys for key, _, _ in keys):
                return DEFER
            # the wave will register these once admitted (shared with the
            # caller's pending_keys update — computed once per candidate)
            self._last_keys = keys
            pages, covered = self._lookup_prefix(pend)
            if pages:
                # map the matched pages; keep >= 1 tail token so the wave's
                # prefill yields logits for this row's first sampled token
                tail_start = min(covered, p_len - 1)
                row = self.page_table[slot]
                for j, p in enumerate(pages):
                    row[j] = p
                    self.page_ref[p] += 1
                if (self._cow_range(slot, tail_start, p_len, cow_pairs)
                        and self._alloc_slot(slot, p_len)):
                    self.prefix_hits += 1
                    self.prefix_hit_tokens += tail_start
                    return tail_start
                # roll back: drop this slot's holds (shared originals
                # survive via their other holders) and prune copies whose
                # fresh destination was just returned to the pool
                self._free_slot_pages(slot)
                cow_pairs[:] = [pr for pr in cow_pairs
                                if self.page_ref[pr[1]] > 0]
                return None
        return 0 if self._alloc_slot(slot, p_len) else None

    def _admit(self, emitted: Dict[int, List[int]]):
        # a wave may end on DEFER (a queued request wants pages the wave in
        # flight is about to publish); its prefill registers them host-side
        # immediately, so a follow-up wave in the SAME scheduling round can
        # map them — admission only yields to decode when the queue is
        # drained, slot/page-blocked, or genuinely empty.  In mixed mode a
        # deferral instead waits for the matching slot's CHUNKED prefill to
        # complete (steps away), so no follow-up wave runs.
        while self._admit_wave(emitted):
            pass

    def _admit_wave(self, emitted: Dict[int, List[int]]) -> bool:
        """One admission wave: one prefill dispatch (classic), or slot
        placement into the PREFILLING state (mixed steps — the chunk
        dispatches follow in `_mixed_step`).  Returns True when a follow-up
        wave should run right away (progress was made AND the wave ended on
        a prefix deferral this round can still resolve)."""
        free = [i for i in range(self.B) if self.slot_req[i] is None]
        wave: List[Tuple[int, Request]] = []
        offs: List[int] = []
        cow_pairs: List[Tuple[int, int]] = []
        # prefixes a mid-prefill slot will publish at completion are pending
        # for every admission until then (mixed mode; empty otherwise)
        pending_keys: set = set().union(*self._inflight_keys.values()) \
            if self._inflight_keys else set()
        deferred = False
        while free and self.queue:
            rec = self._victim.get(self.queue[0].rid)
            if rec is not None and not self._verify_victim(rec):
                # corrupt spill bytes detected (bitflip while host-resident):
                # drop the record and fall through to recompute-from-prompt —
                # the corrupt pages never reach the pool or a served token
                self._drop_victim(self.queue[0].rid)
                self.n_recompute_fallbacks += 1
                rec = None
            if rec is not None:
                # spilled continuation at the queue head: RESTORE instead
                # of re-prefilling — the slot resumes decoding immediately
                # (no wave membership, no prefill dispatch)
                if (self._faults is not None
                        and self._faults.delay_restore(self._step_idx)):
                    break
                if not self._restore(free[0], self.queue[0], rec):
                    break                     # FCFS: wait for pages
                free.pop(0)
                self.queue.popleft()
                continue
            if self.paged:
                # page-granular admission: the prompt (or eviction
                # continuation) must fit in free pages — NOT a whole
                # max_len slot; shared prefix pages are mapped, not copied
                t = self._try_admit_paged(free[0], self.queue[0],
                                          pending_keys, cow_pairs)
                if t is DEFER:
                    deferred = True
                    break
                if t is None:
                    break                     # FCFS: no starvation of longs
                offs.append(t)
                if self.prefix_sharing:
                    pending_keys.update(k for k, _, _ in self._last_keys)
            else:
                offs.append(0)
            wave.append((free.pop(0), self.queue.popleft()))
        if not wave:
            return False
        if self.mixed_steps:
            # no prefill dispatch: the slots enter the PREFILLING state with
            # their pages/prefix mapping/CoW already in place, and
            # `_mixed_step` feeds their chunks interleaved with decode.
            # CoW copies still land NOW — before any chunk reads the
            # privatized pages.
            if self.paged:
                self._apply_copies(cow_pairs)
                self.peak_pages_in_use = max(self.peak_pages_in_use,
                                             self.pages_in_use())
            for (s, r), off in zip(wave, offs):
                pend = r.prompt + r.tokens
                self.slot_req[s] = r
                self.prefilling[s] = True
                self._pend[s] = pend
                self.lengths[s] = off        # prefix-hit KV is already valid
                self.cur_tok[s] = -1
                self.active[s] = False
                self._admit_counter += 1
                self._admit_seq[s] = self._admit_counter
                if self.paged and self.prefix_sharing:
                    self._inflight_keys[s] = {
                        k for k, _, _ in self._registration_keys(pend, True)}
            # a deferral cannot resolve until an in-flight prefill
            # completes (steps, not waves, away) — never loop here
            return False
        n = len(wave)
        prompts = [r.prompt + r.tokens for _, r in wave]
        full_lens = np.array([len(p) for p in prompts], np.int32)
        offs_a = np.array(offs, np.int32)
        # only each row's divergent TAIL runs through the prefill forward;
        # without sharing the tail IS the whole prompt (offsets all 0)
        tails = [p[o:] for p, o in zip(prompts, offs)]
        lens = full_lens - offs_a
        L = self._bucket(int(lens.max()))
        toks = np.zeros((n, L), np.int32)
        for i, p in enumerate(tails):
            toks[i, : len(p)] = p
        slots = np.array([s for s, _ in wave], np.int32)
        rids = np.array([r.rid for _, r in wave], np.int32)
        gens = np.array([len(r.tokens) for _, r in wave], np.int32)
        self.prefill_tokens_computed += int(lens.sum())
        self.model_steps += 1
        if self.paged:
            # CoW copies land before the prefill that reads the private
            # pages; sample the peak while the wave's prompt pages are
            # held — requests that retire at admission (budget 1 / instant
            # EOS) free them below, and the metric must have seen them
            self._apply_copies(cow_pairs)
            self.peak_pages_in_use = max(self.peak_pages_in_use,
                                         self.pages_in_use())
            fn = make_paged_prefill_fn(self.model, n, L, self.temperature,
                                       self.top_k, self.top_p)
            (self.cache, tok0, fin), load = self._split_load(fn(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                self.cache, jnp.asarray(self.page_table[slots]),
                jnp.asarray(offs_a), jnp.asarray(rids), jnp.asarray(gens),
                self.key))
            fin_a = np.asarray(fin)
            if self.prefix_sharing:
                # the wave's prompt KV is now fully valid: publish every
                # page-aligned prefix (the exact-prompt entry waits for
                # retirement — decode still appends into the partial page).
                # Rows whose logits came back non-finite are NOT published:
                # their KV is suspect and must never be shared
                for i, ((s, _), p) in enumerate(zip(wave, prompts)):
                    if fin_a[i]:
                        self._register_prefixes(s, p, exact=False)
        else:
            fn = make_ragged_prefill_fn(self.model, n, L, self.max_len,
                                        self.temperature, self.top_k,
                                        self.top_p)
            (self.cache, tok0, fin), load = self._split_load(fn(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                self.cache, jnp.asarray(slots), jnp.asarray(rids),
                jnp.asarray(gens), self.key))
            fin_a = np.asarray(fin)
        tok0 = np.asarray(tok0)
        self._mark_load(load)
        for i, (s, r) in enumerate(wave):
            self.slot_req[s] = r
            self._admit_counter += 1
            self._admit_seq[s] = self._admit_counter
            if not fin_a[i]:
                # non-finite prompt logits: quarantine just this request —
                # its sentinel token is never emitted, its pages never shared
                self.n_poisoned += 1
                self.lengths[s] = full_lens[i]
                self._retire(s, status="poisoned", register=False)
                continue
            t0 = int(tok0[i])
            budget_left = r.max_new_tokens - len(r.tokens)
            r.tokens.append(t0)
            emitted.setdefault(r.rid, []).append(t0)
            self.lengths[s] = full_lens[i]
            self.cur_tok[s] = t0
            self.remaining[s] = budget_left - 1
            # capacity counts as done: an eviction continuation re-admitted
            # at exactly max_len tokens just produced its final in-capacity
            # token — decoding further would write past the buffer/table
            done = ((self.eos_id is not None and t0 == self.eos_id)
                    or budget_left <= 1 or int(full_lens[i]) >= self.max_len)
            if done:
                self._retire(s)
            else:
                self.active[s] = True
        return deferred

    def _plan_decode_run(self, ahead,
                         evict_on_starve: bool = True) -> np.ndarray:
        """The set of active slots that can append `ahead` more tokens this
        step (paged mode: lazy allocation to cover them — capped at max_len,
        the capacity retirement bound — plus copy-on-write for any still-
        shared page the write range touches; normally none — decode writes
        start past a slot's registered prefix pages, this is the safety net
        for exact-prompt hits).  `ahead` is a scalar or a per-slot (B,)
        array (speculative steps ask for 1 + k_b tokens per slot).
        Starved slots stall (excluded from the returned mask, state
        untouched); if NOTHING can run the youngest active slot is evicted
        until something can — unless `evict_on_starve=False`, which
        reports the all-stalled plan instead so the caller can retry with
        a cheaper ask (the speculative two-pass shrinks starved slots'
        drafts to 0 before any eviction).  Dense mode: every active slot
        runs."""
        run = self.active.copy()
        if not self.paged:
            return run
        ahead_arr = np.broadcast_to(np.asarray(ahead, np.int32), (self.B,))
        cow_pairs: List[Tuple[int, int]] = []
        while True:
            run = self.active.copy()
            for b in np.flatnonzero(self.active):
                upto = min(int(self.lengths[b]) + int(ahead_arr[b]),
                           self.max_len)
                if not (self._alloc_slot(int(b), upto)
                        and self._cow_range(int(b), int(self.lengths[b]),
                                            upto, cow_pairs)):
                    run[b] = False
            if run.any() or not self.active.any() or not evict_on_starve:
                break
            self._evict(self._eviction_victim())
            # pruning: copies whose fresh destination the eviction just
            # freed must not fire (the page may be re-allocated above)
            cow_pairs[:] = [pr for pr in cow_pairs
                            if self.page_ref[pr[1]] > 0]
        self._apply_copies(cow_pairs)
        self.peak_pages_in_use = max(self.peak_pages_in_use,
                                     self.pages_in_use())
        return run

    def _slot_rids_gens(self) -> Tuple[np.ndarray, np.ndarray]:
        """(rids, gens) per slot for `_row_keys` (0s for empty slots —
        their samples are discarded)."""
        rids = np.zeros(self.B, np.int32)
        gens = np.zeros(self.B, np.int32)
        for b, r in enumerate(self.slot_req):
            if r is not None:
                rids[b] = r.rid
                gens[b] = len(r.tokens)
        return rids, gens

    def _decode(self, emitted: Dict[int, List[int]]):
        if not self.active.any():
            return
        with _span("sched.plan"):
            run = self._plan_decode_run(self.decode_chunk)
            if not run.any():
                return
            fn = make_ragged_decode_fn(self.model, self.decode_chunk,
                                       self.temperature, self.top_k,
                                       self.eos_id, self.max_len, self.top_p)
            # stalled rows advertise length 0 for the whole chunk (writes
            # are trash-routed, attention runs zero KV partitions —
            # genuinely free, not just discarded) and have ALL their state
            # restored host-side
            rids, gens = self._slot_rids_gens()
            self.model_steps += self.decode_chunk
            run_lengths = self.lengths * run
            run_poison = self._poison_mask & run
        with _span("sched.dispatch", program="decode_scan",
                   chunk=self.decode_chunk):
            args = (self.params, jnp.asarray(self.cur_tok), self.cache,
                    jnp.asarray(run_lengths), jnp.asarray(run),
                    jnp.asarray(self.remaining), jnp.asarray(rids),
                    jnp.asarray(gens), self.key, jnp.asarray(run_poison))
            if self.paged:
                out = fn(*args, jnp.asarray(self.page_table))
            else:
                out = fn(*args)
            out, load = self._split_load(out)
            tok, self.cache, lengths, active, remaining, toks, em, pois = out
        with _span("sched.readback"):
            tok = np.array(tok)
            lengths = np.array(lengths)
            active = np.array(active)
            remaining = np.array(remaining)
            toks = np.asarray(toks)                    # (chunk, B)
            em = np.asarray(em)
            pois = np.asarray(pois)
            load = None if load is None else np.asarray(load)
        self._mark_load(load)
        with _span("sched.commit"):
            stalled = self.active & ~run
            self.cur_tok = np.where(run, tok, self.cur_tok)
            self.lengths = np.where(run, lengths, self.lengths)
            self.active = active | stalled
            self.remaining = remaining
            for b in range(self.B):
                r = self.slot_req[b]
                if r is None:
                    continue
                step_toks = toks[em[:, b], b].tolist()
                if step_toks:
                    r.tokens.extend(int(t) for t in step_toks)
                    emitted.setdefault(r.rid, []).extend(
                        int(t) for t in step_toks)
                if pois[b]:
                    # non-finite logits hit this row mid-scan: quarantine
                    # just this request (tokens before the poison were
                    # emitted and are kept); neighbors' rows are untouched —
                    # batch rows are independent, so their streams stay
                    # bit-identical
                    self.n_poisoned += 1
                    self._retire(b, status="poisoned", register=False)
                elif not self.active[b] and not self.prefilling[b]:
                    # occupied, not decoding, not mid-chunked-prefill: the
                    # scan just finished it (prefilling slots are not in the
                    # scan — they retire through _finish_prefill's
                    # bookkeeping instead)
                    self._retire(b)

    # -- mixed prefill+decode steps -----------------------------------------
    def _finish_prefill(self, slot: int, tok0: int,
                        emitted: Dict[int, List[int]]):
        """A chunk just completed `slot`'s prompt: publish its prefixes,
        record its first sampled token, and either retire it or promote it
        into the decode pool — the mixed-mode twin of the unchunked
        admission post-wave bookkeeping."""
        r = self.slot_req[slot]
        pend = self._pend[slot]
        self.prefilling[slot] = False
        self._pend[slot] = None
        if self.paged and self.prefix_sharing:
            self._inflight_keys.pop(slot, None)
            # the prompt KV is now fully valid: page-aligned prefixes go
            # live (the exact-prompt entry still waits for retirement)
            self._register_prefixes(slot, pend, exact=False)
        budget_left = r.max_new_tokens - len(r.tokens)
        r.tokens.append(tok0)
        emitted.setdefault(r.rid, []).append(tok0)
        self.lengths[slot] = len(pend)
        self.cur_tok[slot] = tok0
        self.remaining[slot] = budget_left - 1
        done = ((self.eos_id is not None and tok0 == self.eos_id)
                or budget_left <= 1 or len(pend) >= self.max_len)
        if done:
            self._retire(slot)
        else:
            self.active[slot] = True

    def _post_decode_token(self, slot: int, tok: int,
                           emitted: Dict[int, List[int]]):
        """Host-side retirement bookkeeping for ONE decode token emitted by
        a mixed step — the same conditions the fused chunk-scan applies
        in-scan (EOS / budget exhausted / cache capacity)."""
        r = self.slot_req[slot]
        r.tokens.append(tok)
        emitted.setdefault(r.rid, []).append(tok)
        self.remaining[slot] -= 1
        new_len = int(self.lengths[slot]) + 1
        done = ((self.eos_id is not None and tok == self.eos_id)
                or self.remaining[slot] <= 0 or new_len >= self.max_len)
        if done:
            self._retire(slot)
        else:
            self.lengths[slot] = new_len
            self.cur_tok[slot] = tok

    def _plan_chunks(self) -> List[Tuple[int, int, int]]:
        """This step's prefill chunks as (slot, start, end): the per-step
        `prefill_chunk_budget` handed out FCFS in admission order, each
        chunk cut by `plan_prefill_chunk` (page-aligned interior
        boundaries).  The degradation ladder halves the budget at level
        >= 2 (`_effective_chunk_budget`)."""
        budget = self._effective_chunk_budget()
        chunks: List[Tuple[int, int, int]] = []
        for b in sorted(np.flatnonzero(self.prefilling),
                        key=lambda b: self._admit_seq[b]):
            if budget <= 0:
                break
            start = int(self.lengths[b])
            end = plan_prefill_chunk(start, len(self._pend[b]), budget,
                                     self.page_size if self.paged else 0)
            chunks.append((int(b), start, end))
            budget -= end - start
        return chunks

    def _chunk_prefill_wave(self, emitted: Dict[int, List[int]]):
        """Paged mixed step, prefill half: ONLY the prefilling slots ride
        this dispatch (the pool has no batch axis — any subset of page-table
        rows can), so the decode lane never pays their chunk width.  The
        device program is the SAME `make_paged_prefill_fn` an unchunked
        admission wave runs, at per-row chunk offsets — which is why chunked
        bytes and tokens are bit-identical to unchunked admission."""
        with _span("sched.plan"):
            chunks = self._plan_chunks()
            if not chunks:
                return
            n = len(chunks)
            L = self._bucket(max(e - s for _, s, e in chunks))
            toks = np.zeros((n, L), np.int32)
            for i, (b, s, e) in enumerate(chunks):
                toks[i, : e - s] = self._pend[b][s:e]
            slots = np.array([b for b, _, _ in chunks], np.int32)
            offs = np.array([s for _, s, _ in chunks], np.int32)
            lens = np.array([e - s for _, s, e in chunks], np.int32)
            rids = np.array([self.slot_req[b].rid for b, _, _ in chunks],
                            np.int32)
            gens = np.array([len(self.slot_req[b].tokens)
                             for b, _, _ in chunks], np.int32)
            self.prefill_tokens_computed += int(lens.sum())
            self.model_steps += 1
            fn = make_paged_prefill_fn(self.model, n, L, self.temperature,
                                       self.top_k, self.top_p)
            rows = self.page_table[slots]
        with _span("sched.dispatch", program="prefill_wave", n=n, L=L):
            (self.cache, tok0, fin), load = self._split_load(fn(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                self.cache, jnp.asarray(rows), jnp.asarray(offs),
                jnp.asarray(rids), jnp.asarray(gens), self.key))
        with _span("sched.readback"):
            tok0 = np.asarray(tok0)
            fin = np.asarray(fin)
            load = None if load is None else np.asarray(load)
        self._mark_load(load)
        with _span("sched.commit"):
            for i, (b, s, e) in enumerate(chunks):
                self.lengths[b] = e
                if e == len(self._pend[b]):
                    if fin[i]:
                        self._finish_prefill(b, int(tok0[i]), emitted)
                    else:
                        self.n_poisoned += 1
                        self._retire(b, status="poisoned", register=False)

    def _mixed_step_fused(self, emitted: Dict[int, List[int]]):
        """Fused mixed step: ONE (B, L) dispatch — every decoding slot that
        can extend contributes 1 token at column 0, prefilling slots their
        chunk, idle rows nothing.  Attention routes the two row classes
        through their unchunked kernels inside the one program
        (`blocks._mixed_attend` + the ragged-Q q_len early-outs)."""
        with _span("sched.plan"):
            run = self._plan_decode_run(1)
            chunks = self._plan_chunks()
            if not chunks and not run.any():
                return
            L = self._bucket(max([e - s for _, s, e in chunks] + [1]))
            toks = np.zeros((self.B, L), np.int32)
            offs = np.zeros(self.B, np.int32)
            seq = np.zeros(self.B, np.int32)
            dec = np.zeros(self.B, bool)
            for b, s, e in chunks:
                toks[b, : e - s] = self._pend[b][s:e]
                offs[b] = s
                seq[b] = e - s
            for b in np.flatnonzero(run):
                toks[b, 0] = self.cur_tok[b]
                offs[b] = self.lengths[b]
                seq[b] = 1
                dec[b] = True
            self.prefill_tokens_computed += sum(e - s for _, s, e in chunks)
            self.model_steps += 1
            rids, gens = self._slot_rids_gens()
            fn = make_mixed_step_fn(self.model, self.B, L, self.temperature,
                                    self.top_k, self.top_p)
            poison = self._poison_mask & (seq > 0)
        with _span("sched.dispatch", program="mixed", n=self.B, L=L):
            args = (self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(offs), jnp.asarray(seq), jnp.asarray(dec),
                    jnp.asarray(rids), jnp.asarray(gens), self.key,
                    jnp.asarray(poison))
            if self.paged:
                out = fn(*args, jnp.asarray(self.page_table))
            else:
                out = fn(*args)
            (self.cache, tok, fin), load = self._split_load(out)
        with _span("sched.readback"):
            tok = np.asarray(tok)
            fin = np.asarray(fin)
            load = None if load is None else np.asarray(load)
        self._mark_load(load)
        with _span("sched.commit"):
            for b, s, e in chunks:
                self.lengths[b] = e
                if e == len(self._pend[b]):
                    if fin[b]:
                        self._finish_prefill(b, int(tok[b]), emitted)
                    else:
                        self.n_poisoned += 1
                        self._retire(b, status="poisoned", register=False)
            for b in np.flatnonzero(dec):
                if fin[b]:
                    self._post_decode_token(b, int(tok[b]), emitted)
                else:
                    self.n_poisoned += 1
                    self._retire(b, status="poisoned", register=False)

    def _mixed_step(self, emitted: Dict[int, List[int]]):
        """One mixed scheduler step — no slot ever waits for another slot's
        prompt.  `mixed_dispatch="fused"` (default) advances both row
        classes in ONE (B, L) device program; `"paired"` (paged mode only)
        instead runs a prefilling-slots-only chunk wave back-to-back with
        the regular decode chunk-scan — see the class docstring for the
        trade-off."""
        if self.mixed_dispatch == "paired":
            self._chunk_prefill_wave(emitted)
            self._decode(emitted)
        else:
            self._mixed_step_fused(emitted)

    # -- speculative decoding -----------------------------------------------
    def _propose(self, slot: int) -> List[int]:
        """Draft tokens for `slot`, clamped so an all-accepted step can
        never overrun the token budget (k <= remaining - 1: the step emits
        k + 1 tokens) or the cache capacity (k + 1 KV writes starting at
        the slot's fill)."""
        r = self.slot_req[slot]
        if r.spec_k is None:
            r.spec_k = self.draft_len
        cap = min(r.spec_k, int(self.remaining[slot]) - 1,
                  self.max_len - int(self.lengths[slot]) - 1)
        if cap < 1:
            return []
        return propose_draft_tokens(r.prompt + r.tokens, cap,
                                    eos_id=self.eos_id)

    def _spec_step(self, emitted: Dict[int, List[int]],
                   with_chunks: bool = False):
        """One speculative step: propose drafts per decoding slot, verify
        them (plus any mixed-mode prefill chunks when `with_chunks`) in ONE
        dispatch, then emit each row's accepted prefix + bonus/correction
        token through the standard per-token retirement bookkeeping.

        Paged allocation is two-pass: pass 1 asks for each slot's full
        1 + k_b writes WITHOUT evicting on starvation; slots the pool
        cannot stretch to simply drop their drafts (k_b = 0 — a plain
        1-token step needs no new page in the common case), and only if
        even that starves does pass 2 fall back to the regular
        evict-youngest path.  Speculation therefore never evicts a
        neighbor just to chase draft tokens."""
        with _span("sched.plan"):
            chunks = self._plan_chunks() if with_chunks else []
            drafts: List[List[int]] = [[] for _ in range(self.B)]
            karr = np.zeros(self.B, np.int32)
            for b in np.flatnonzero(self.active):
                drafts[b] = self._propose(int(b))
                karr[b] = len(drafts[b])
            run = self._plan_decode_run(1 + karr, evict_on_starve=False)
            starved = self.active & ~run
            if starved.any():
                karr[starved] = 0
                for b in np.flatnonzero(starved):
                    drafts[b] = []
                run = self._plan_decode_run(1 + karr)
            if not chunks and not run.any():
                return
            P = self.draft_len + 1
            # rectangle width: P covers every verify row; only widen (to the
            # prefill bucket) when a mixed-mode chunk actually rides along —
            # _bucket(1) is the full prefill_bucket, which would make every
            # chunkless spec step pay for 16 columns of masked padding
            L = (max(P, self._bucket(max(e - s for _, s, e in chunks)))
                 if chunks else P)
            toks = np.zeros((self.B, L), np.int32)
            offs = np.zeros(self.B, np.int32)
            seq = np.zeros(self.B, np.int32)
            dec = np.zeros(self.B, bool)
            for b, s, e in chunks:
                toks[b, : e - s] = self._pend[b][s:e]
                offs[b] = s
                seq[b] = e - s
            for b in np.flatnonzero(run):
                k = int(karr[b])
                toks[b, 0] = self.cur_tok[b]
                if k:
                    toks[b, 1: 1 + k] = drafts[b]
                offs[b] = self.lengths[b]
                seq[b] = 1 + k
                dec[b] = True
            self.prefill_tokens_computed += sum(e - s for _, s, e in chunks)
            self.model_steps += 1
            self.n_spec_steps += 1
            rids, gens = self._slot_rids_gens()
            fn = make_spec_step_fn(self.model, self.B, L, P, self.temperature,
                                   self.top_k, self.top_p)
            poison = self._poison_mask & (seq > 0)
        with _span("sched.dispatch", program="spec", n=self.B, L=L):
            args = (self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(offs), jnp.asarray(seq), jnp.asarray(dec),
                    jnp.asarray(rids), jnp.asarray(gens), self.key,
                    jnp.asarray(poison))
            if self.paged:
                res = fn(*args, jnp.asarray(self.page_table))
            else:
                res = fn(*args)
            (self.cache, out, n_emit, fin), load = self._split_load(res)
        with _span("sched.readback"):
            out = np.asarray(out)
            n_emit = np.asarray(n_emit)
            fin = np.asarray(fin)
            load = None if load is None else np.asarray(load)
        self._mark_load(load)
        with _span("sched.commit"):
            for b, s, e in chunks:
                self.lengths[b] = e
                if e == len(self._pend[b]):
                    if fin[b]:
                        self._finish_prefill(b, int(out[b, 0]), emitted)
                    else:
                        self.n_poisoned += 1
                        self._retire(b, status="poisoned", register=False)
            for b in np.flatnonzero(dec):
                if not fin[b]:
                    # poisoned verify row: nothing from this step is
                    # emitted — the request retires alone, draft accounting
                    # untouched
                    self.n_poisoned += 1
                    self._retire(b, status="poisoned", register=False)
                    continue
                r = self.slot_req[b]
                k = int(karr[b])
                m = int(n_emit[b])
                if k:
                    a = m - 1
                    self.spec_proposed += k
                    self.spec_accepted += a
                    self.spec_rejected += k - a
                    if a == k:
                        r.spec_k = min(self.draft_len, r.spec_k + 1)
                    elif a == 0:
                        r.spec_k = max(1, r.spec_k // 2)
                for j in range(m):
                    self._post_decode_token(b, int(out[b, j]), emitted)
                    if self.slot_req[b] is None:
                        break      # retired mid-prefix: later tokens discarded

    # -- SLA degradation ladder ---------------------------------------------
    def _effective_chunk_budget(self) -> int:
        """Per-step prefill token budget after ladder degradation (level
        >= 2 halves it — prefill chunks are the widest dispatches on the
        step critical path, so halving them is the straightest TBT lever
        short of refusing work)."""
        if self.ladder_level >= 2:
            return max(1, self.prefill_chunk_budget // 2)
        return self.prefill_chunk_budget

    def _under_pressure(self) -> bool:
        """Either pressure signal over target: queue-depth p95 (last 32
        steps) above `queue_depth_target`, or p95 time-between-tokens
        above `tbt_target_ms` (measured with the injectable clock)."""
        depths = self._queue_depths[-32:]
        if depths and (float(np.percentile(np.asarray(depths), 95))
                       > self.queue_depth_target):
            return True
        if self._tbt_samples:
            p95_ms = float(np.percentile(
                np.asarray(self._tbt_samples), 95)) * 1e3
            if p95_ms > self.tbt_target_ms:
                return True
        return False

    def _ladder_update(self):
        """Move at most one rung per cooldown window: escalate while the
        pressure signal holds, release (reverse order) once it clears.
        Rung effects are applied where the level is READ — speculation
        dispatch (>=1), `_effective_chunk_budget` (>=2), admission pause
        (>=3) — so a restore resumes mid-ladder with no extra state."""
        if self.tbt_target_ms <= 0:
            return
        if (self._step_idx - self._ladder_last_change
                < self.ladder_cooldown_steps):
            return
        if self._under_pressure():
            if self.ladder_level < len(LADDER_RUNGS):
                self.ladder_transitions[LADDER_RUNGS[self.ladder_level]] += 1
                self.ladder_level += 1
                self.ladder_escalations += 1
                self._ladder_last_change = self._step_idx
        elif self.ladder_level > 0:
            self.ladder_level -= 1
            self.ladder_deescalations += 1
            self._ladder_last_change = self._step_idx

    def _sample_tbt(self):
        if self.tbt_target_ms <= 0:
            return
        now = self._clock()
        if self._last_step_time is not None:
            self._tbt_samples.append(now - self._last_step_time)
        self._last_step_time = now

    # -- fault hooks with scheduler-side state ------------------------------
    def _bitflip_victim_page(self):
        """Fault hook: XOR one byte of the lowest-rid victim record's host
        bytes (page 0 of its fetched tree's first pool leaf).  The spill
        crcs no longer match, so the restore-time verify must detect the
        flip and route the request through recompute-from-prompt — the
        corrupt bytes never reach the pool."""
        for rid in sorted(self._victim):
            rec = self._victim[rid]
            if rec.n_host and rec.data is not None:
                leaves, treedef = jax.tree.flatten(rec.data)
                leaf = np.array(leaves[0])   # writable contiguous copy
                leaf.view(np.uint8).reshape(-1)[0] ^= 0xFF
                leaves[0] = leaf
                rec.data = jax.tree.unflatten(treedef, leaves)
                self.bitflips_injected += 1
                return

    # -- crash recovery: snapshot / restore ---------------------------------
    def _config_fingerprint(self) -> Dict[str, Any]:
        """Every config knob a snapshot's bit-identical continuation
        depends on — verified on restore (a mismatched scheduler would
        resume with silently different streams)."""
        return {
            "arch": self.model.cfg.name,
            "kv_bits": self.model.cfg.kv_bits,
            "B": self.B, "max_len": self.max_len, "eos_id": self.eos_id,
            "temperature": self.temperature, "top_k": self.top_k,
            "top_p": self.top_p, "decode_chunk": self.decode_chunk,
            "prefill_bucket": self.prefill_bucket,
            "page_size": self.page_size if self.paged else 0,
            "num_pages": self.num_pages if self.paged else 0,
            "prefix_sharing": self.prefix_sharing,
            "mixed_steps": self.mixed_steps,
            "mixed_dispatch": self.mixed_dispatch,
            "speculate": self.speculate, "draft_len": self.draft_len,
            "draft_mode": self.draft_mode,
        }

    def snapshot(self, directory: Optional[str] = None) -> str:
        """Write a restorable snapshot generation (default: snapshot_dir)
        through the checkpoint machinery (atomic tmp+rename, per-leaf
        crc32, fsync) and return its path.

        Three leaves: the KV pool bytes (device_get of the live cache
        tree), the sampling key, and one pickled metadata blob — queue and
        slot state, page tables/refcounts, prefix directory + quarantine +
        write-time page checksums, victim records (host bytes included),
        ladder/fault/counter state, and every Request ever submitted.
        Called at step END (quiescent: no dispatch in flight), so restore
        + re-drive continues every stream bit-identically."""
        from repro.checkpoint import checkpoint as ckpt
        directory = directory or self.snapshot_dir
        if not directory:
            raise ValueError("snapshot() needs a directory "
                             "(snapshot_dir or an explicit argument)")
        meta: Dict[str, Any] = {
            "config": self._config_fingerprint(),
            "step_idx": self._step_idx,
            "next_rid": self._next_rid,
            "requests": self.requests,
            "queue": [r.rid for r in self.queue],
            "slot_req": [None if r is None else r.rid
                         for r in self.slot_req],
            "lengths": self.lengths, "active": self.active,
            "remaining": self.remaining, "cur_tok": self.cur_tok,
            "prefilling": self.prefilling, "pend": self._pend,
            "inflight_keys": self._inflight_keys,
            "admit_seq": self._admit_seq,
            "admit_counter": self._admit_counter,
            "victim": self._victim, "victim_used": self._victim_used,
            "queue_depths": self._queue_depths,
            "counters": {
                "n_evictions": self.n_evictions, "n_spills": self.n_spills,
                "n_restores": self.n_restores,
                "spilled_pages": self.spilled_pages,
                "spill_bytes": self.spill_bytes,
                "n_recompute_fallbacks": self.n_recompute_fallbacks,
                "n_deadline_misses": self.n_deadline_misses,
                "n_rejections": self.n_rejections,
                "n_reclaim_stalls": self.n_reclaim_stalls,
                "refcount_corruptions_detected":
                    self.refcount_corruptions_detected,
                "model_steps": self.model_steps,
                "n_spec_steps": self.n_spec_steps,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_rejected": self.spec_rejected,
                "prefix_hits": self.prefix_hits,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefill_tokens_computed": self.prefill_tokens_computed,
                "n_cow_copies": self.n_cow_copies,
                "prefix_evictions": self.prefix_evictions,
                "corruptions_detected": self.corruptions_detected,
                "bitflips_injected": self.bitflips_injected,
                "n_poisoned": self.n_poisoned,
                "n_snapshots": self.n_snapshots,
            },
            "ladder": {
                "level": self.ladder_level,
                "escalations": self.ladder_escalations,
                "deescalations": self.ladder_deescalations,
                "paused_steps": self.ladder_paused_steps,
                "transitions": dict(self.ladder_transitions),
                "last_change": self._ladder_last_change,
            },
            "integrity": {
                "page_crc": dict(self.page_crc),
                "quarantined": set(self.quarantined),
                "poison_mask": self._poison_mask.copy(),
            },
            "faults": (None if self._faults is None else
                       (dict(self._faults.fired),
                        self._faults._rng.get_state())),
        }
        if self.paged:
            meta["paged"] = {
                "page_table": self.page_table,
                "page_ref": self.page_ref,
                "free_pages": list(self.free_pages),
                "prefix_dir": self.prefix_dir,
                "dir_ref": dict(self._dir_ref),
                "peak_pages_in_use": self.peak_pages_in_use,
            }
        tree = {"cache": jax.device_get(self.cache),
                "meta": np.frombuffer(pickle.dumps(meta), np.uint8),
                "rng": np.asarray(self.key)}
        path = ckpt.save(directory, self._step_idx, tree)
        self.n_snapshots += 1
        return path

    def restore(self, directory: Optional[str] = None) -> int:
        """Load the newest intact snapshot generation into THIS scheduler
        (constructed with the SAME config — the fingerprint is verified)
        and return the restored step index.  `run()` afterwards continues
        every in-flight stream bit-identically to an uncrashed run.

        Integrity (`!= "off"`): directory-held pages are re-checksummed
        against their write-time crcs after the pool bytes land — a
        mismatch (corruption that predates the snapshot) quarantines every
        holding prefix entry; victim records are verified lazily at
        re-admission, falling back to recompute-from-prompt."""
        from repro.checkpoint import checkpoint as ckpt
        directory = directory or self.snapshot_dir
        if not directory:
            raise ValueError("restore() needs a directory "
                             "(snapshot_dir or an explicit argument)")
        like = {"cache": self.cache, "meta": np.zeros(0, np.uint8),
                "rng": np.asarray(self.key)}
        tree, step = ckpt.restore_latest(directory, like)
        if tree is None:
            raise FileNotFoundError(
                f"no restorable snapshot generation in {directory}")
        meta = pickle.loads(tree["meta"].tobytes())
        mine = self._config_fingerprint()
        if meta["config"] != mine:
            diff = {k: (meta["config"].get(k), mine.get(k))
                    for k in set(meta["config"]) | set(mine)
                    if meta["config"].get(k) != mine.get(k)}
            raise ValueError(
                f"snapshot config mismatch (snapshot vs this): {diff}")
        self.cache = jax.tree.map(jnp.asarray, tree["cache"])
        self.key = jnp.asarray(tree["rng"])
        self._step_idx = int(meta["step_idx"])
        self._next_rid = int(meta["next_rid"])
        self.requests = meta["requests"]
        self.queue = collections.deque(
            self.requests[rid] for rid in meta["queue"])
        self.slot_req = [None if rid is None else self.requests[rid]
                         for rid in meta["slot_req"]]
        self.lengths = np.asarray(meta["lengths"], np.int32).copy()
        self.active = np.asarray(meta["active"], bool).copy()
        self.remaining = np.asarray(meta["remaining"], np.int32).copy()
        self.cur_tok = np.asarray(meta["cur_tok"], np.int32).copy()
        self.prefilling = np.asarray(meta["prefilling"], bool).copy()
        self._pend = list(meta["pend"])
        self._inflight_keys = dict(meta["inflight_keys"])
        self._admit_seq = np.asarray(meta["admit_seq"], np.int64).copy()
        self._admit_counter = int(meta["admit_counter"])
        self._victim = dict(meta["victim"])
        self._victim_used = int(meta["victim_used"])
        self._queue_depths = list(meta["queue_depths"])
        for k, v in meta["counters"].items():
            setattr(self, k, v)
        lad = meta["ladder"]
        self.ladder_level = int(lad["level"])
        self.ladder_escalations = int(lad["escalations"])
        self.ladder_deescalations = int(lad["deescalations"])
        self.ladder_paused_steps = int(lad["paused_steps"])
        self.ladder_transitions = dict(lad["transitions"])
        self._ladder_last_change = int(lad["last_change"])
        # wall-clock TBT samples do not survive a crash meaningfully
        self._tbt_samples.clear()
        self._last_step_time = None
        self.page_crc = dict(meta["integrity"]["page_crc"])
        self.quarantined = set(meta["integrity"]["quarantined"])
        # the sticky poison mark survives the crash: a victim tagged but
        # not yet retired at snapshot time still retires after restore
        self._poison_mask[:] = np.asarray(
            meta["integrity"]["poison_mask"], bool)
        if self.paged:
            pg = meta["paged"]
            self.page_table = np.asarray(pg["page_table"], np.int32).copy()
            self.page_ref = np.asarray(pg["page_ref"], np.int32).copy()
            self.free_pages = list(pg["free_pages"])
            self.prefix_dir = collections.OrderedDict(pg["prefix_dir"])
            self._dir_ref = dict(pg["dir_ref"])
            self.peak_pages_in_use = int(pg["peak_pages_in_use"])
        if self._faults is not None:
            if meta["faults"] is not None:
                fired, rng_state = meta["faults"]
                self._faults.fired = dict(fired)
                self._faults._rng.set_state(rng_state)
            if self._faults.plan.crash_at_step:
                # a restore means the crash already happened: a plan that
                # still carries crash_at_step must never fire again (loop)
                self._faults.fired["crash"] = max(
                    1, self._faults.fired.get("crash", 0))
        if self.integrity != "off" and self.page_crc:
            pages = sorted(self.page_crc)
            crcs = self._compute_page_crcs(pages)
            bad = {p for p, c in zip(pages, crcs) if c != self.page_crc[p]}
            if bad:
                self.corruptions_detected += len(bad)
                doomed = [k for k, (pp, _) in self.prefix_dir.items()
                          if bad & set(pp)]
                for key in doomed:
                    self._quarantine_entry(key)
        return int(step)

    def results(self) -> Dict[int, List[int]]:
        """Full per-request token stream for every request ever submitted
        (done or not) — what crash-recovery tests diff against a run that
        never crashed."""
        return {rid: list(r.tokens) for rid, r in self.requests.items()}

    def step(self) -> Dict[int, List[int]]:
        """One scheduling round: shed stale queued requests, admit (and
        restore spilled continuations), then either one mixed
        prefill+decode dispatch (mixed mode with a prefill in flight) or
        one fused decode chunk-scan; retire as slots finish.  Returns the
        tokens generated this round, keyed by request id.  Fault-injection
        hooks and the per-step invariant audit (`REPRO_AUDIT=1` /
        `audit_every_step=True`) run here.  Each phase is a profiler span
        (`sched.admit`, ..., `sched.commit`; see `_span`)."""
        emitted: Dict[int, List[int]] = {}
        self._step_idx += 1
        if (self._faults is not None
                and self._faults.should_crash(self._step_idx)):
            # before any work this step — the last periodic snapshot is the
            # newest durable state, exactly like a real mid-trace crash
            raise CrashInjected(f"injected crash at step {self._step_idx}")
        with _span("sched.admit"):
            self._shed_stale()
            self._shed_admitted()
            self._queue_depths.append(len(self.queue))
            self._ladder_update()
            if (self.ladder_level >= 3
                    and any(r is not None for r in self.slot_req)):
                # deepest rung: pause admission while residents drain.  Never
                # with ALL slots empty — then admission must run or nothing
                # would ever drain the queue (livelock)
                self.ladder_paused_steps += 1
            else:
                self._admit(emitted)
            if (self._faults is not None and self.active.any()
                    and self._faults.force_evict(self._step_idx)):
                self._evict(self._eviction_victim())
            if (self._faults is not None and self._victim
                    and self._faults.bitflip_spilled_page(self._step_idx)):
                self._bitflip_victim_page()
            occupied = self.active | self.prefilling
            if (self._faults is not None and occupied.any()
                    and self._faults.poison_nan(self._step_idx)):
                # poison the occupied slot with the lowest rid —
                # deterministic across runs, so the chaos suite can diff
                # against a run without that request.  Mid-prefill slots
                # count (mixed-steps chunking keeps them `prefilling`, not
                # `active`, for several steps) and the mark is STICKY
                # (cleared only when the slot is vacated): a victim whose
                # logits nothing samples at the fault step retires at its
                # next sampled logits instead of silently shrugging the
                # fault off
                victim = min((int(b) for b in np.flatnonzero(occupied)),
                             key=lambda b: self.slot_req[b].rid)
                self._poison_mask[victim] = True
        if self.speculate and self.ladder_level < 1:
            if (self.mixed_steps and self.prefilling.any()
                    and self.mixed_dispatch == "paired"):
                self._chunk_prefill_wave(emitted)
                self._spec_step(emitted)
            else:
                self._spec_step(
                    emitted,
                    with_chunks=self.mixed_steps and self.prefilling.any())
        elif self.mixed_steps and self.prefilling.any():
            self._mixed_step(emitted)
        else:
            self._decode(emitted)
        with _span("sched.commit"):
            if self.paged:
                self.peak_pages_in_use = max(self.peak_pages_in_use,
                                             self.pages_in_use())
            if (self._faults is not None and self.paged
                    and self._faults.corrupt_refcount(self._step_idx)):
                self._corrupt_and_detect()
            if self._audit_every:
                self.audit()
            if (self.snapshot_every
                    and self._step_idx % self.snapshot_every == 0):
                self.snapshot()
            self._sample_tbt()
        return emitted

    # -- invariant audit ----------------------------------------------------
    def _corrupt_and_detect(self):
        """Fault hook: bump a live page's refcount by one and require
        `audit()` to DETECT the corruption (raising otherwise), then roll
        it back — an end-to-end proof the auditor is live, not a no-op."""
        held = np.flatnonzero(self.page_ref > 0)
        if held.size == 0:
            return
        p = int(held[0])
        self.page_ref[p] += 1
        try:
            self.audit()
        except AuditError:
            self.refcount_corruptions_detected += 1
        else:
            raise AssertionError(
                f"audit() missed an injected refcount corruption on page {p}")
        finally:
            self.page_ref[p] -= 1

    def audit(self):
        """Full scheduler invariant check; raises `AuditError` with every
        violation found.  Paged mode verifies the page-accounting triangle:
        every page's refcount equals its holder count (slot page-table
        rows + prefix-directory entries + victim-pool records), refcount 0
        iff on the free list (no orphans, no double-frees), page-table
        rows are contiguous valid prefixes covering their slot's kv fill,
        and the victim pool's host-page accounting respects its cap.
        Cheap (host metadata only) — `REPRO_AUDIT=1` runs it after every
        step; tests call it at end-of-run."""
        errs: List[str] = []
        for b in range(self.B):
            occupied = self.slot_req[b] is not None
            if not occupied and self.active[b]:
                errs.append(f"slot {b}: active without a request")
            if not occupied and self.prefilling[b]:
                errs.append(f"slot {b}: prefilling without a request")
            if self.active[b] and self.prefilling[b]:
                errs.append(f"slot {b}: both active and prefilling")
            if self.prefilling[b] and self._pend[b] is None:
                errs.append(f"slot {b}: prefilling with no pending tokens")
        if self.paged:
            P = self.num_pages
            free_set = set(self.free_pages)
            if len(free_set) != len(self.free_pages):
                errs.append("free list holds duplicate pages (double-free)")
            if TRASH_PAGE in free_set:
                errs.append("reserved trash page is on the free list")
            for p in free_set:
                if not 0 < p < P:
                    errs.append(f"free list holds out-of-range page {p}")
            expected = np.zeros(P, np.int64)
            for b in range(self.B):
                row = self.page_table[b]
                k = int((row >= 0).sum())
                if k and not (row[:k] >= 0).all():
                    errs.append(f"slot {b}: page-table row is not a "
                                "contiguous allocated prefix")
                for p in row[row >= 0]:
                    p = int(p)
                    if not 0 < p < P:
                        errs.append(f"slot {b}: invalid page id {p}")
                    else:
                        expected[p] += 1
                if self.slot_req[b] is None and k:
                    errs.append(f"slot {b}: empty slot still maps {k} pages")
                if (self.slot_req[b] is not None and self.lengths[b] > 0
                        and k < self._pages_for(int(self.lengths[b]))):
                    errs.append(
                        f"slot {b}: kv fill {int(self.lengths[b])} not "
                        f"covered by its {k} allocated pages")
            dir_ref: Dict[int, int] = {}
            for pages, _ in self.prefix_dir.values():
                for p in pages:
                    dir_ref[p] = dir_ref.get(p, 0) + 1
                    if 0 < p < P:
                        expected[p] += 1
                    else:
                        errs.append(f"directory maps invalid page {p}")
            if dir_ref != self._dir_ref:
                errs.append("directory page refcounts (_dir_ref) out of "
                            "sync with the directory's entries")
            # integrity invariants: a quarantined prefix must never
            # re-enter the directory, and recorded write-time checksums
            # only ever cover directory-held (CoW-immutable) pages
            for key in self.quarantined:
                if key in self.prefix_dir:
                    errs.append("quarantined prefix key re-entered the "
                                "directory")
            for p in self.page_crc:
                if p not in self._dir_ref:
                    errs.append(f"page {p}: write-time checksum recorded "
                                "but page is not directory-held")
            if self.integrity == "paranoid":
                # paranoid mode extends the audit to victim-pool BYTES:
                # every spilled record's host pages must still match their
                # spill-time checksums (host-side hash, no device traffic)
                for rid, rec in self._victim.items():
                    if rec.crcs is None or not rec.n_host:
                        continue
                    crcs = T.cache_page_checksums(
                        rec.data, list(range(rec.n_host)))
                    if any(int(a) != int(b)
                           for a, b in zip(crcs, rec.crcs)):
                        errs.append(
                            f"victim record {rid}: host page bytes no "
                            "longer match their spill-time checksums")
            used = 0
            for rid, rec in self._victim.items():
                used += rec.n_host
                for kind, p in rec.logical:
                    if kind == "ref":
                        if 0 < p < P:
                            expected[p] += 1
                        else:
                            errs.append(
                                f"victim record {rid} holds invalid page {p}")
            if used != self._victim_used:
                errs.append(f"victim pool accounting: records hold {used} "
                            f"host pages, counter says {self._victim_used}")
            if self.victim_pool_pages and used > self.victim_pool_pages:
                errs.append(f"victim pool over capacity: {used} > "
                            f"{self.victim_pool_pages}")
            for p in range(1, P):
                ref = int(self.page_ref[p])
                if ref != int(expected[p]):
                    errs.append(f"page {p}: refcount {ref} != "
                                f"{int(expected[p])} holders")
                if ref == 0 and p not in free_set:
                    errs.append(f"page {p}: orphaned (refcount 0 but not "
                                "on the free list)")
                if ref != 0 and p in free_set:
                    errs.append(f"page {p}: on the free list with "
                                f"refcount {ref}")
            if int(self.page_ref[TRASH_PAGE]) != 0:
                errs.append("reserved trash page has a nonzero refcount")
        if errs:
            raise AuditError("scheduler audit failed:\n  "
                             + "\n  ".join(errs))

    @property
    def stats(self) -> Dict[str, Any]:
        """Overload / robustness counters (host-side, O(1) to read)."""
        depths = np.asarray(self._queue_depths or [0])
        return {
            "steps": self._step_idx,
            "model_steps": self.model_steps,
            "spec_steps": self.n_spec_steps,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_rejected": self.spec_rejected,
            "spec_accept_rate": (self.spec_accepted
                                 / max(self.spec_proposed, 1)),
            "evictions": self.n_evictions,
            "spills": self.n_spills,
            "restores": self.n_restores,
            "spilled_pages": self.spilled_pages,
            "spill_bytes": self.spill_bytes,
            "kv_bytes_per_token": kv_bytes_per_token(self.model.cfg),
            "recompute_fallbacks": self.n_recompute_fallbacks,
            "deadline_misses": self.n_deadline_misses,
            "rejections": self.n_rejections,
            "reclaim_stalls": self.n_reclaim_stalls,
            "refcount_corruptions_detected":
                self.refcount_corruptions_detected,
            "victim_pool_pages_used": self._victim_used,
            "queue_depth_p50": float(np.percentile(depths, 50)),
            "queue_depth_p95": float(np.percentile(depths, 95)),
            # integrity + recovery
            "corruptions_detected": self.corruptions_detected,
            "bitflips_injected": self.bitflips_injected,
            "poisoned": self.n_poisoned,
            "quarantined_prefixes": len(self.quarantined),
            "snapshots": self.n_snapshots,
            # degradation ladder
            "ladder_level": self.ladder_level,
            "ladder_escalations": self.ladder_escalations,
            "ladder_deescalations": self.ladder_deescalations,
            "ladder_paused_steps": self.ladder_paused_steps,
            "ladder_transitions": dict(self.ladder_transitions),
            "tbt_p95_ms": (float(np.percentile(
                np.asarray(self._tbt_samples), 95)) * 1e3
                if self._tbt_samples else 0.0),
        }

    def run(self, on_tokens: Optional[Callable[[int, List[int]], None]] = None
            ) -> Dict[int, List[int]]:
        """Drive steps until all submitted requests complete.  `on_tokens`
        (rid, new_tokens) streams deltas as they are generated."""
        results: Dict[int, List[int]] = {}
        while self.queue or any(r is not None for r in self.slot_req):
            for rid, toks in self.step().items():
                results.setdefault(rid, []).extend(toks)
                if on_tokens is not None:
                    on_tokens(rid, toks)
        return results


# ===========================================================================
# generate entrypoints
# ===========================================================================
def generate(model: Model, params, prompt_batch: Dict[str, jax.Array],
             max_new_tokens: int, max_len: int,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             rng: Optional[jax.Array] = None,
             continuous_batching: bool = False,
             eos_id: Optional[int] = None,
             decode_chunk: int = 8,
             max_batch_slots: Optional[int] = None,
             page_size: int = 0, num_pages: int = 0,
             prefix_sharing: bool = False,
             prefix_cache_pages: int = 0,
             mixed_steps: bool = False,
             prefill_chunk_budget: int = 0,
             mixed_dispatch: str = "fused",
             victim_pool_pages: int = 0,
             max_queue: int = 0,
             speculate: bool = False,
             draft_len: int = 4,
             draft_mode: str = "ngram",
             deadline_ms: Optional[float] = None,
             ttl_steps: Optional[int] = None,
             fault_plan: Optional[FaultPlan] = None,
             kv_bits: int = 0,
             integrity: str = "off",
             tbt_target_ms: float = 0.0,
             snapshot_every: int = 0,
             snapshot_dir: Optional[str] = None,
             restore_from: Optional[str] = None) -> jax.Array:
    """Batched generation. Returns (B, max_new_tokens) generated ids.

    Default: equal-length prefill + scan-fused decode (the paper's token
    pipeline, §3.6).  With `continuous_batching=True` this is a thin wrapper
    over one `Scheduler` run — per-slot ragged decode with EOS (`eos_id`)
    retirement over `max_batch_slots` KV slots (default: the batch size);
    rows that finish early are padded with `eos_id` (or 0).  `page_size > 0`
    additionally switches the scheduler's KV storage to the paged pool
    (`num_pages` pages; 0 = match the dense slot footprint),
    `prefix_sharing=True` layers refcounted prefix sharing + copy-on-write
    on top (`prefix_cache_pages` caps the retained prefix directory), and
    `mixed_steps=True` chunks admission prefill into mixed prefill+decode
    steps of at most `prefill_chunk_budget` prompt tokens (bit-identical
    outputs; bounded time between tokens).  `victim_pool_pages` enables
    the host-memory spill pool for eviction continuations, `max_queue` /
    `deadline_ms` / `ttl_steps` the admission-control bounds (rejected
    rows stay padding), `speculate=True` self-speculative multi-token
    decode steps (`draft_len` drafts per slot per step, `draft_mode`
    selects the proposer; greedy outputs stay bit-identical), and
    `fault_plan` the deterministic fault-injection hooks — see
    `Scheduler`.

    temperature=0 reproduces greedy decoding exactly; temperature>0 samples
    (optionally top_k- and/or nucleus-top_p-truncated) with `rng`
    (default PRNGKey(0)).

    `kv_bits` (0 = keep the model's config) overrides KV-cache storage
    precision for this run — 4 packs two dynamic-map codes per byte,
    halving cache bytes/token.

    Recovery & integrity (continuous batching only): `integrity` enables
    per-page checksums ("checksum" | "paranoid"), `tbt_target_ms` the SLA
    degradation ladder, `snapshot_every`/`snapshot_dir` periodic crash
    snapshots, and `restore_from` resumes from the newest snapshot in a
    directory before submitting this batch — see `Scheduler`.
    """
    if kv_bits and kv_bits != model.cfg.kv_bits:
        model = build_model(dataclasses.replace(model.cfg,
                                                kv_bits=int(kv_bits)))
    B, S = prompt_batch["tokens"].shape
    rng = jax.random.PRNGKey(0) if rng is None else rng
    if speculate and not continuous_batching:
        raise ValueError("speculate requires continuous_batching=True "
                         "(drafts are verified by the scheduler's ragged "
                         "decode rows)")
    if not continuous_batching and (integrity != "off" or tbt_target_ms > 0
                                    or snapshot_every or restore_from):
        raise ValueError("integrity / tbt_target_ms / snapshot_every / "
                         "restore_from require continuous_batching=True "
                         "(they are Scheduler features)")
    if continuous_batching:
        sched = Scheduler(model, params,
                          max_batch_slots=max_batch_slots or B,
                          max_len=max_len, eos_id=eos_id,
                          temperature=temperature, top_k=top_k, top_p=top_p,
                          decode_chunk=decode_chunk, rng=rng,
                          page_size=page_size, num_pages=num_pages,
                          prefix_sharing=prefix_sharing,
                          prefix_cache_pages=prefix_cache_pages,
                          mixed_steps=mixed_steps,
                          prefill_chunk_budget=prefill_chunk_budget,
                          mixed_dispatch=mixed_dispatch,
                          victim_pool_pages=victim_pool_pages,
                          max_queue=max_queue, speculate=speculate,
                          draft_len=draft_len, draft_mode=draft_mode,
                          fault_plan=fault_plan,
                          integrity=integrity, tbt_target_ms=tbt_target_ms,
                          snapshot_every=snapshot_every,
                          snapshot_dir=snapshot_dir)
        if restore_from:
            sched.restore(restore_from)
        tokens = np.asarray(prompt_batch["tokens"])
        rids = []
        for b in range(B):
            try:
                rids.append(sched.submit(tokens[b].tolist(), max_new_tokens,
                                         deadline_ms=deadline_ms,
                                         ttl_steps=ttl_steps))
            except Overloaded:
                # bounded-queue backpressure: the row stays padding
                rids.append(None)
        results = sched.run()
        pad = 0 if eos_id is None else int(eos_id)
        out = np.full((B, max_new_tokens), pad, np.int32)
        for b, rid in enumerate(rids):
            if rid is None:
                continue
            got = results.get(rid, [])[:max_new_tokens]
            out[b, : len(got)] = got
        return jnp.asarray(out)
    if page_size:
        raise ValueError("page_size requires continuous_batching=True")
    prefill = make_prefill_step(model)
    cache = model.init_cache(B, max_len)
    logits, cache, enc_out = prefill(params, prompt_batch, cache)
    rng, sub = jax.random.split(rng)
    tok0 = sample_logits(logits, sub, temperature, top_k, top_p)[:, None]
    decode = make_generate_fn(model, S, max_new_tokens, temperature, top_k,
                              top_p)
    return decode(params, tok0, cache, rng, enc_out)


def greedy_generate(model: Model, params, prompt_batch: Dict[str, jax.Array],
                    max_new_tokens: int, max_len: int):
    """Batched greedy decoding (temperature 0 wrapper around `generate`)."""
    return generate(model, params, prompt_batch, max_new_tokens, max_len)
