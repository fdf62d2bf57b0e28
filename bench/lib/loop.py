"""The serving loop: offers a mix's requests to the program's `Scheduler`,
steps it, and records every token's delivery time and every step's work.

One thread does everything: submit what is due, run one `Scheduler.step`,
hand out what it returned.  Each of the three is a profiler span (`submit`,
`step`, `deliver`), so a trace can say what the host did in a device gap.

The work of each step is recorded from what the loop knows: prompt lengths,
the tokens `step` returned, and after the step which slots are still
prefilling and how far (`Scheduler.slot_req`, `prefilling`, `lengths`,
read only).  A prefill row is (tokens computed, KV length after); a decode
iteration lists the KV length each decoding row attended.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

from bench.lib.latency import Record


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    prefill: List[Tuple[int, int]]          # (tokens computed, KV length)
    decode: List[List[int]]                 # per scan iteration: KV lengths
    delivered: int


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class ServingLoop:
    """Drives `sched` with `traffic`.  Open loop (`poisson`): requests are
    due on a schedule drawn before the run, whatever the server does.
    Closed loop (`closed`, `sessions`): each client sends its next request
    the moment its last one finished."""

    def __init__(self, sched, traffic, clock: Callable[[], float] = None):
        self.sched = sched
        self.traffic = traffic
        self.clock = clock or time.perf_counter
        self.recs: Dict[int, Record] = {}
        self.steps: List[StepRecord] = []
        self.lateness: List[float] = []     # submit time - due time (s)
        self._progress: Dict[int, int] = {}  # rid -> prompt tokens computed
        self._next_due: Optional[float] = None
        self._clients_due: List[float] = []  # closed loop: send times
        self.offering = True

    # -- offering load ------------------------------------------------------
    def start(self, t: float):
        if self.traffic.kind == "poisson":
            self._next_due = t + self.traffic.next_gap()
        else:
            self._clients_due = [t] * self.traffic.concurrency

    def _submit(self, due: float):
        prompt, max_new = self.traffic.next_request()
        rid = self.sched.submit(prompt, max_new)
        self.recs[rid] = Record(rid, due, prompt, max_new)
        self.lateness.append(self.clock() - due)

    def _offer(self, now: float):
        if not self.offering:
            return
        with _span("submit"):
            if self._next_due is not None:
                while self._next_due <= now:
                    self._submit(self._next_due)
                    self._next_due += self.traffic.next_gap()
            else:
                due = [t for t in self._clients_due if t <= now]
                self._clients_due = [t for t in self._clients_due if t > now]
                for t in due:
                    self._submit(t)

    def _busy(self) -> bool:
        s = self.sched
        return bool(s.queue) or any(r is not None for r in s.slot_req)

    # -- one step -------------------------------------------------------------
    def step(self) -> StepRecord:
        s = self.sched
        t0 = self.clock()
        with _span("step"):
            emitted = s.step()
        t1 = self.clock()
        with _span("deliver"):
            rec = self._account(emitted, t0, t1)
        self.steps.append(rec)
        return rec

    def _account(self, emitted, t0: float, t1: float) -> StepRecord:
        s = self.sched
        prefill: List[Tuple[int, int]] = []
        decode: List[List[int]] = []
        after: Dict[int, int] = {}
        for b, r in enumerate(s.slot_req):
            if r is not None and s.prefilling[b]:
                after[r.rid] = int(s.lengths[b])
        delivered = 0
        for rid, toks in emitted.items():
            rec = self.recs.get(rid)
            if rec is None:         # submitted by someone else (warm-up)
                continue
            k0 = rec.delivered
            if k0 == 0:
                after[rid] = rec.prompt_len
            # token k >= 1 came from the forward of position P + k - 1
            # (iteration i of a chunk-scan), attending P + k keys
            for i, k in enumerate(range(max(k0, 1), k0 + len(toks))):
                while len(decode) <= i:
                    decode.append([])
                decode[i].append(rec.prompt_len + k)
            rec.tokens.append((t1, len(toks)))
            rec.ids.extend(int(t) for t in toks)
            delivered += len(toks)
            if rec.finished and self.offering and self._next_due is None:
                self._clients_due.append(t1)
        for rid, done in after.items():
            n = done - self._progress.get(rid, 0)
            if n > 0:
                prefill.append((n, done))
            self._progress[rid] = done
        return StepRecord(t0, t1, prefill, decode, delivered)

    # -- phases ---------------------------------------------------------------
    def run_until(self, t_end: float, stop: Callable[[], bool] = None,
                  between: Callable[[float], None] = None):
        """Offer load and step until the clock passes `t_end` or `stop()`
        holds; a step that starts before `t_end` runs to its end.
        `between(now)` runs before each offer, outside every span."""
        while True:
            now = self.clock()
            if now >= t_end or (stop is not None and stop()):
                return
            if between is not None:
                between(now)
            self._offer(now)
            if self._busy():
                self.step()
                continue
            nxt = min([t_end, self._next_due or t_end] + self._clients_due)
            time.sleep(max(0.0, min(nxt - self.clock(), 0.05)))

    def drain(self, done: Callable[[], bool], t_limit: float):
        """Stop offering load and step what is in flight until `done()` or
        the clock passes `t_limit`."""
        self.offering = False
        while self._busy() and not done() and self.clock() < t_limit:
            self.step()
