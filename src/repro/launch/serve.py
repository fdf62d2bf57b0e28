"""Serving entrypoint: batched prefill + scan-fused decode over the PIM KV
cache (greedy by default; --temperature/--top-k for sampling).

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16 --temperature 0.8 --top-k 40

--continuous-batching serves the same prompts through the ragged slot
scheduler (per-sequence KV lengths, EOS retirement via --eos-id, slot count
via --max-batch-slots) instead of the padded equal-length loop; adding
--page-size N (and optionally --num-pages) swaps the scheduler's KV storage
for the shared paged pool (page-granular admission, lazy allocation,
free-on-retire); --prefix-cache additionally shares page-aligned prompt
prefixes between requests (refcounted pages + copy-on-write, retained
across retirements up to --prefix-cache-pages); --mixed-steps chunks
admission prefill into mixed prefill+decode steps (at most
--prefill-chunk-budget prompt tokens per step) so a long prompt never
stalls the decoding slots.  --top-p enables nucleus sampling on any path.
--victim-pool-pages N gives the paged scheduler a host-memory spill pool
(evictions move private KV pages device->host and restore them on
re-admission instead of recomputing the prompt), and --deadline-ms /
--max-queue bound the admission queue (stale queued requests are shed,
over-depth submits rejected with backpressure).  --speculate drafts up to
--draft-len tokens per slot by prompt lookup (--draft-mode ngram) and
verifies them in one ragged multi-token launch per step — greedy outputs
stay bit-identical and sampling stays distribution-preserving.
--integrity checksum|paranoid adds per-KV-page crc32 with
detect-and-recompute (corrupt bytes are never served), --tbt-target-ms
arms the SLA degradation ladder (disable speculation -> halve prefill
chunks -> pause admission), and --snapshot-every N / --snapshot-dir D /
--restore-from D give the scheduler crash snapshot/restore with
bit-identical continuation streams.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import pipeline as data
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build_model
from repro.runtime import serve_lib, sharding as sh


def enable_compile_cache():
    """Keep compiled programs across processes: in $JAX_COMPILATION_CACHE_DIR
    when it is set (jax reads the variable itself), else at the one fixed
    `.jax_cache` directory of the checkout.  The path is part of the cache
    key, so it must not move between runs."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = Path(__file__).resolve().parents[3]
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--mesh", default="")
    ap.add_argument("--attn-impl", default="",
                    choices=["", "behavioral", "kernel"])
    ap.add_argument("--no-decode-kernel", action="store_true",
                    help="disable the flash-decode kernel on the "
                         "kernel path (force the prefill kernel for Sq==1)")
    ap.add_argument("--decode-block-k", type=int, default=0,
                    help="KV block size of the decode grid")
    ap.add_argument("--kv-bits", type=int, default=0, choices=[0, 4, 8],
                    help="KV-cache storage precision: 8 = int8 values "
                         "(default), 4 = blockwise dynamic-map codes packed "
                         "two per byte — halves KV bytes/token (0 = keep "
                         "the arch config)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 samples with temperature softmax")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the top-k logits (0 = all)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest logit set with "
                         "cumulative probability >= top-p (1.0 = all)")
    ap.add_argument("--seed", type=int, default=0, help="sampling rng seed")
    ap.add_argument("--continuous-batching", action="store_true",
                    help="serve through the ragged slot scheduler (per-"
                         "sequence KV lengths + EOS retirement)")
    ap.add_argument("--max-batch-slots", type=int, default=0,
                    help="KV cache slots for the scheduler (0 = --batch)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="retire sequences on this token id (-1 = never)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page: >0 switches the scheduler to "
                         "the paged pool (requires --continuous-batching)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool pages incl. the reserved trash page "
                         "(0 = match the dense slot footprint)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted prefix sharing + copy-on-write pages: "
                         "requests with a common page-aligned prompt prefix "
                         "map the SAME physical pages and skip the shared "
                         "prefill (requires --page-size)")
    ap.add_argument("--prefix-cache-pages", type=int, default=0,
                    help="cap on distinct pages the retained prefix "
                         "directory may pin after requests retire "
                         "(LRU-evicted; 0 = pool-pressure-driven only)")
    ap.add_argument("--mixed-steps", action="store_true",
                    help="chunked prefill: every scheduler step is one "
                         "mixed batch of decode tokens + prompt chunks, so "
                         "admission never stalls decoding slots (requires "
                         "--continuous-batching; bit-identical outputs)")
    ap.add_argument("--prefill-chunk-budget", type=int, default=0,
                    help="max prompt tokens one mixed step may prefill "
                         "across all prefilling slots (0 = default 32)")
    ap.add_argument("--mixed-dispatch", default="fused",
                    choices=["fused", "paired"],
                    help="mixed-step shape: one (B, L) rectangle per step "
                         "('fused', default) or a prefilling-rows-only "
                         "chunk wave paired with the decode scan "
                         "('paired'; paged mode only — cheaper when "
                         "compute dominates dispatch overhead)")
    ap.add_argument("--victim-pool-pages", type=int, default=0,
                    help="host-memory victim pool (pages): evictions SPILL "
                         "their private KV pages device->host and restore "
                         "them on re-admission instead of recomputing the "
                         "prompt (requires --page-size; 0 = recompute only)")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="per-request deadline: queued requests older than "
                         "this are shed as deadline misses (0 = none)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue: submits beyond this "
                         "depth are rejected with backpressure (0 = "
                         "unbounded)")
    ap.add_argument("--speculate", action="store_true",
                    help="speculative decoding: draft tokens by prompt "
                         "lookup and verify them in one ragged multi-token "
                         "launch per step (requires --continuous-batching; "
                         "greedy outputs bit-identical, sampling "
                         "distribution-preserving)")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="max drafted tokens per speculative step (the "
                         "per-slot depth adapts between 1 and this cap)")
    ap.add_argument("--draft-mode", default="ngram", choices=["ngram"],
                    help="draft proposer: 'ngram' = self-speculative "
                         "prompt lookup (no draft model)")
    ap.add_argument("--integrity", default="off",
                    choices=["off", "checksum", "paranoid"],
                    help="KV-page integrity: 'checksum' records per-page "
                         "crc32 at directory-registration/spill time and "
                         "verifies on restore (mismatch -> recompute, never "
                         "served); 'paranoid' additionally verifies on "
                         "every prefix hit and eviction (requires "
                         "--page-size)")
    ap.add_argument("--tbt-target-ms", type=float, default=0.0,
                    help="p95 time-between-tokens SLA target: enables the "
                         "degradation ladder (disable speculation -> halve "
                         "prefill chunks -> pause admission, released in "
                         "reverse as pressure clears; 0 = off)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="write a crash-recovery scheduler snapshot every N "
                         "steps (requires --snapshot-dir; 0 = off)")
    ap.add_argument("--snapshot-dir", default="",
                    help="directory for scheduler snapshot generations "
                         "(atomic, checksummed; newest intact wins)")
    ap.add_argument("--restore-from", default="",
                    help="resume from the newest intact snapshot in this "
                         "directory before serving (config must match)")
    args = ap.parse_args(argv)
    if args.page_size and not args.continuous_batching:
        ap.error("--page-size requires --continuous-batching")
    if args.num_pages and not args.page_size:
        ap.error("--num-pages requires --page-size")
    if args.prefix_cache and not args.page_size:
        ap.error("--prefix-cache requires --page-size")
    if args.prefix_cache_pages and not args.prefix_cache:
        ap.error("--prefix-cache-pages requires --prefix-cache")
    if args.mixed_steps and not args.continuous_batching:
        ap.error("--mixed-steps requires --continuous-batching")
    if args.prefill_chunk_budget and not args.mixed_steps:
        ap.error("--prefill-chunk-budget requires --mixed-steps")
    if args.mixed_dispatch == "paired" and not args.page_size:
        ap.error("--mixed-dispatch paired requires --page-size")
    if args.victim_pool_pages and not args.page_size:
        ap.error("--victim-pool-pages requires --page-size")
    if args.victim_pool_pages < 0:
        ap.error("--victim-pool-pages must be >= 0")
    if args.deadline_ms < 0:
        ap.error("--deadline-ms must be >= 0")
    if args.max_queue < 0:
        ap.error("--max-queue must be >= 0")
    if (args.deadline_ms or args.max_queue) and not args.continuous_batching:
        ap.error("--deadline-ms/--max-queue require --continuous-batching")
    if args.speculate and not args.continuous_batching:
        ap.error("--speculate requires --continuous-batching")
    if args.draft_len < 1:
        ap.error("--draft-len must be >= 1")
    if args.integrity != "off" and not args.page_size:
        ap.error("--integrity requires --page-size (checksums are "
                 "page-granular)")
    if args.tbt_target_ms < 0:
        ap.error("--tbt-target-ms must be >= 0")
    if args.tbt_target_ms and not args.continuous_batching:
        ap.error("--tbt-target-ms requires --continuous-batching")
    if args.snapshot_every < 0:
        ap.error("--snapshot-every must be >= 0")
    if args.snapshot_every and not args.snapshot_dir:
        ap.error("--snapshot-every requires --snapshot-dir")
    if ((args.snapshot_every or args.restore_from)
            and not args.continuous_batching):
        ap.error("--snapshot-every/--restore-from require "
                 "--continuous-batching")

    cfg = get_config(args.arch, smoke=args.smoke)
    import dataclasses
    if args.attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=args.attn_impl)
    if args.no_decode_kernel:
        cfg = dataclasses.replace(cfg, decode_kernel=False)
    if args.decode_block_k:
        cfg = dataclasses.replace(cfg, decode_block_k=args.decode_block_k)
    if args.kv_bits:
        cfg = dataclasses.replace(cfg, kv_bits=args.kv_bits)
    model = build_model(cfg)
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model")[: len(shape)])

    params = model.init(jax.random.PRNGKey(0))
    if mesh is not None:
        params = jax.device_put(params, sh.param_shardings(params, cfg, mesh))

    shape = type("S", (), {"global_batch": args.batch,
                           "seq_len": args.prompt_len})()
    batch = {k: jnp.asarray(v)
             for k, v in data.make_batch(cfg, shape, 0).items()}
    max_len = args.prompt_len + args.new_tokens

    t0 = time.time()
    eos = None if args.eos_id < 0 else args.eos_id
    out = serve_lib.generate(
        model, params, batch, args.new_tokens, max_len,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        rng=jax.random.PRNGKey(args.seed),
        continuous_batching=args.continuous_batching, eos_id=eos,
        max_batch_slots=args.max_batch_slots or None,
        page_size=args.page_size, num_pages=args.num_pages,
        prefix_sharing=args.prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages,
        mixed_steps=args.mixed_steps,
        prefill_chunk_budget=args.prefill_chunk_budget,
        mixed_dispatch=args.mixed_dispatch,
        victim_pool_pages=args.victim_pool_pages,
        max_queue=args.max_queue,
        deadline_ms=args.deadline_ms or None,
        speculate=args.speculate, draft_len=args.draft_len,
        draft_mode=args.draft_mode,
        integrity=args.integrity,
        tbt_target_ms=args.tbt_target_ms,
        snapshot_every=args.snapshot_every,
        snapshot_dir=args.snapshot_dir or None,
        restore_from=args.restore_from or None)
    jax.block_until_ready(out)
    dt = time.time() - t0
    if args.continuous_batching and eos is not None:
        # count only tokens actually emitted (sequences may retire at EOS;
        # everything after a row's first EOS is padding)
        import numpy as np
        toks = 0
        for row in np.asarray(out):
            hits = np.flatnonzero(row == eos)
            toks += int(hits[0]) + 1 if hits.size else row.size
    else:
        toks = args.batch * args.new_tokens
    if args.page_size:
        mode = f"scheduler/paged(ps={args.page_size})"
        if args.prefix_cache:
            mode += "+prefix-cache"
        if args.victim_pool_pages:
            mode += f"+spill({args.victim_pool_pages}p)"
    elif args.continuous_batching:
        mode = "scheduler"
    else:
        mode = "scan-fused"
    if args.mixed_steps:
        mode += "+mixed-steps"
    if cfg.kv_bits != 8:
        mode += f"+kv{cfg.kv_bits}"
    if args.speculate:
        mode += f"+speculative({args.draft_mode},k={args.draft_len})"
    dev = jax.devices()[0]
    print(f"[serve] device={dev.platform}:{dev.device_kind}"
          f"x{jax.device_count()} "
          f"arch={cfg.name} attn={cfg.attn_impl} mode={mode} "
          f"temp={args.temperature} top_k={args.top_k} top_p={args.top_p} "
          f"generated {out.shape} in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. prefill+compile)")
    print("[serve] first sequences:", out[:2, :12].tolist())
    return out


if __name__ == "__main__":
    main()
