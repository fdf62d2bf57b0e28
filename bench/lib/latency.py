"""Time to first token, time per output token and the output rate, from the
timestamps the serving loop records.  All times are host-clock seconds.

- TTFT: from a request's *scheduled* arrival to its first token, over every
  request that arrived inside the window.  One still without a token when
  the drain ends counts at the drain's end.
- TPOT: per request, over the deliveries it received inside the window,
  (last delivery time - first delivery time) / (tokens delivered after the
  first delivery).  With one token per delivery this is (last - first) /
  (tokens - 1); a decode chunk-scan delivers several at once, and those of
  the first delivery were made before its time.  A request with one
  delivery in the window gives no sample.
- Output rate: tokens delivered inside the window over the window's length.

The end-to-end latencies are the medians of these samples (`ttft_p50_ms`,
`tpot_p50_ms`): a window of the slow cells holds about ten requests, too few
for any tail.  The 90th percentiles are printed with their sample counts.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence, Tuple


@dataclasses.dataclass
class Record:
    rid: int
    arrival: float                  # scheduled arrival (open loop) or send
    prompt: List[int]
    max_new: int
    tokens: List[Tuple[float, int]] = dataclasses.field(default_factory=list)
    ids: List[int] = dataclasses.field(default_factory=list)   # served

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def delivered(self) -> int:
        return len(self.ids)

    @property
    def finished(self) -> bool:
        return self.delivered >= self.max_new

    def first_token_time(self) -> Optional[float]:
        return self.tokens[0][0] if self.tokens else None


def percentile(values: Sequence[float], q: int) -> float:
    """The q-th percentile (inclusive method); NaN without samples."""
    if not values:
        return float("nan")
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100, method="inclusive")[q - 1])


def ttft_samples(recs: Sequence[Record], t0: float, t1: float,
                 drain_end: float) -> List[float]:
    out = []
    for r in recs:
        if t0 <= r.arrival < t1:
            first = r.first_token_time()
            out.append((drain_end if first is None else first) - r.arrival)
    return out


def tpot_samples(recs: Sequence[Record], t0: float,
                 t1: float) -> List[float]:
    out = []
    for r in recs:
        inside = [(t, n) for t, n in r.tokens if t0 < t <= t1]
        if len(inside) >= 2:
            after_first = sum(n for _, n in inside[1:])
            out.append((inside[-1][0] - inside[0][0]) / after_first)
    return out


def tokens_in(recs: Sequence[Record], t0: float, t1: float) -> int:
    return sum(n for r in recs for t, n in r.tokens if t0 < t <= t1)


def summarize(recs: Sequence[Record], t0: float, t1: float,
              drain_end: float) -> Dict[str, float]:
    """The end-to-end numbers of one window, with their sample counts."""
    ttft = ttft_samples(recs, t0, t1, drain_end)
    tpot = tpot_samples(recs, t0, t1)
    toks = tokens_in(recs, t0, t1)
    return {
        "ttft_p90_ms": percentile(ttft, 90) * 1e3,
        "ttft_p50_ms": percentile(ttft, 50) * 1e3,
        "ttft_samples": len(ttft),
        "ttft_without_token": sum(
            1 for r in recs if t0 <= r.arrival < t1 and not r.tokens),
        "tpot_p90_ms": percentile(tpot, 90) * 1e3,
        "tpot_p50_ms": percentile(tpot, 50) * 1e3,
        "tpot_samples": len(tpot),
        "output_tokens": toks,
        "output_tokens_per_s": toks / (t1 - t0),
        "window_s": t1 - t0,
    }
