"""The comparison that decides `correct` for a served model.

After the window, a sample of the requests the program served, drawn from
the seed and always holding the one with the most served tokens, is run
through the configuration's plain reference over prompt + served tokens.
At every served position, the gap by which the served token's logit lies
below the reference's best logit is read; the widest gap of the sample is
held against the cell's limit.  Greedy decoding: a sound program serves the
reference's best token up to the rounding its int8 arithmetic allows.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench.lib.latency import Record


def pick(recs: Sequence[Record], k: int, seed: int) -> List[Record]:
    """`k` requests with served tokens: the longest served first, then a
    seeded draw, finished requests before unfinished ones."""
    served = [r for r in recs if r.ids]
    if not served:
        return []
    longest = max(served, key=lambda r: (len(r.ids), -r.rid))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng(int(seed))
    rng.shuffle(rest)
    rest.sort(key=lambda r: not r.finished)      # stable: finished first
    return [longest] + rest[: max(k - 1, 0)]


def widest_gap(logits: np.ndarray, served: Sequence[int]) -> float:
    logits = np.asarray(logits, np.float64)
    served = np.asarray(served, np.int64)
    best = logits.max(-1)
    got = logits[np.arange(len(served)), served]
    return float((best - got).max())


def compare(ref_logits, weights, sample: Sequence[Record], vocab: int,
            token_cap: int) -> Dict[str, float]:
    """Run the reference over each sampled request's prompt and served
    tokens (at most `token_cap` served tokens in all, the longest request's
    first) and return the widest gap with what it was read over."""
    gaps, n_tok, bad = [], 0, 0
    for r in sample:
        ids = list(r.ids[: max(token_cap - n_tok, 0)])
        if not ids:
            break
        bad += sum(1 for t in ids if not 0 <= t < vocab)
        if bad:
            break
        seq = list(r.prompt) + ids[:-1]
        # the logits at position P - 1 + i choose served token i
        pos = [len(r.prompt) - 1 + i for i in range(len(ids))]
        gaps.append(widest_gap(ref_logits(weights, seq, pos), ids))
        n_tok += len(ids)
    return {"logit_gap": max(gaps) if gaps and not bad else float("inf"),
            "tokens_compared": n_tok, "requests_compared": len(gaps),
            "out_of_vocab": bad}
