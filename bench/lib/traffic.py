"""One general generator for every traffic mix, driven by its data file.

A mix names an arrival process and two length distributions:

    "arrivals":   {"kind": "poisson",  "rate_per_s": r, "warm_s": w}
                  {"kind": "closed",   "clients": n,    "warm_s": w}
                  {"kind": "sessions", "sessions": n}
    "prompt_len", "output_len":
                  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
                  {"dist": "uniform", "min": a, "max": b}
                  {"dist": "fixed", "value": v}

    "order":      "shuffle" (the default) or "halton"

Every seed gets the same work: lengths and Poisson gaps are the
distributions' quantiles in one fixed order per mix, and the seed draws
only the token ids (and, in the harness, the weights).  A window holds few
requests, so a seed that reordered them would change which lengths fall
inside it, and runs with different seeds would spread wider than runs of
one seed.  The order:

- "shuffle": a grid of the quantiles (i + 0.5) / n, in one fixed shuffle;
- "halton": request k takes the quantiles at the k-th point of the Halton
  sequence (radical inverses in base 2 for the prompt, 3 for the output, 5
  for the gap before it), so that any run of a few consecutive requests
  spreads over each distribution, and prompt, output and gap are not tied:
  a window of ten arrivals is a stratified sample, not a lucky or unlucky
  draw.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Callable, Dict, Iterator, List, Tuple

import numpy as np

GRID = 512          # quantiles per distribution
ORDER = 0           # the one shuffle of every grid
HALTON_BASES = {"prompt_len": 2, "output_len": 3, "gaps": 5}


def radical_inverse(k: int, base: int) -> float:
    """The k-th point (k >= 1) of the van der Corput sequence in `base`."""
    f, r = 1.0, 0.0
    while k > 0:
        f /= base
        r += f * (k % base)
        k //= base
    return r


def quantile_grid(dist: Dict, n: int = GRID) -> np.ndarray:
    """`n` integer lengths at the quantiles (i + 0.5) / n of `dist`."""
    return quantiles(dist, (np.arange(n) + 0.5) / n)


def quantiles(dist: Dict, u: np.ndarray) -> np.ndarray:
    """Integer lengths of `dist` at the quantiles `u` (0 < u < 1)."""
    u = np.asarray(u, np.float64)
    n = u.shape[0]
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    return np.clip(x, lo, hi).astype(np.int64)


def poisson_gaps(rate: float, n: int = GRID, u=None) -> np.ndarray:
    """Exponential inter-arrival gaps at the quantiles `u`, by default the
    `n` stratified quantiles (i + 0.5) / n."""
    u = (np.arange(n) + 0.5) / n if u is None else np.asarray(u, np.float64)
    return -np.log1p(-u) / float(rate)


class Traffic:
    """The requests of one mix and seed, in the order they are offered.

    `next_request()` returns (prompt token ids, max new tokens); lengths
    cycle through a fixed shuffle of the quantile grid, and the seed draws
    the ids.  For an open loop, `next_gap()` gives the time to the next
    arrival.
    """

    def __init__(self, mix: Dict, seed: int, vocab: int, max_len: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.max_len = int(max_len)
        self.rng = np.random.default_rng(int(seed))
        self._order = np.random.default_rng(ORDER)
        order = mix.get("order", "shuffle")
        if order not in ("shuffle", "halton"):
            raise ValueError(f"unknown order {order!r}")
        arr = mix["arrivals"]
        self.kind = arr["kind"]
        if self.kind not in ("poisson", "closed", "sessions"):
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        rate = arr.get("rate_per_s")
        if order == "halton":
            self._prompts = self._halton(
                lambda u: quantiles(mix["prompt_len"], u), "prompt_len")
            self._outputs = self._halton(
                lambda u: quantiles(mix["output_len"], u), "output_len")
            self._gaps = self._halton(
                lambda u: poisson_gaps(rate, u=u), "gaps")
        else:
            self._prompts = self._cycle(quantile_grid(mix["prompt_len"]))
            self._outputs = self._cycle(quantile_grid(mix["output_len"]))
            self._gaps = (self._cycle(poisson_gaps(rate))
                          if self.kind == "poisson" else None)

    def _cycle(self, grid: np.ndarray) -> Iterator:
        while True:
            for x in self._order.permutation(grid):
                yield x

    @staticmethod
    def _halton(at: Callable, what: str) -> Iterator:
        k = 0
        while True:
            k += 1
            yield at([radical_inverse(k, HALTON_BASES[what])])[0]

    def next_request(self) -> Tuple[List[int], int]:
        p = int(next(self._prompts))
        o = int(next(self._outputs))
        # room for the output inside the cache (sessions fill to max_len)
        o = max(1, min(o, self.max_len - 1 - p))
        prompt = self.rng.integers(0, self.vocab, size=p).tolist()
        return prompt, o

    def next_gap(self) -> float:
        return float(next(self._gaps))

    @property
    def concurrency(self) -> int:
        """Clients (closed loop) or sessions held open; 0 for an open loop."""
        arr = self.mix["arrivals"]
        return int(arr.get("clients", arr.get("sessions", 0)))
