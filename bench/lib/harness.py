"""One run of one cell: set up, measure a window, check the output.

    set-up   device check, compile cache, weights from the seed, the
             scheduler with the mix's server options, one request per chunk
             length the cell uses (compiles every step program), then the
             mix's warm period (open and closed loops) or its sessions'
             prefill (sessions)
    window   `seconds` of the same traffic; with `trace`, a profiler trace
             of a few seconds in its middle, begun and ended between steps
    drain    no new load; in-flight requests step until each that arrived
             in the window has its first token, for at most `drain_s`
    check    peak memory read, the scheduler's state freed, then the
             reference over a seeded sample of the served requests

`run_cell` returns the result object of the run and the numbers that
were compared, each with its limit.
"""
from __future__ import annotations

import gc
import json
import math
import shutil
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional, Tuple

from bench.lib import check, latency, spec
from bench.lib.loop import ServingLoop
from bench.lib.model import make_params
from bench.lib.traffic import Traffic

SCHEDULER_OPTIONS = ("max_batch_slots", "max_len", "page_size", "num_pages",
                     "mixed_steps", "prefill_chunk_budget")
TRACE_MIN_S = 4.0       # traced part of the window: a quarter, at least this


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


def device_check(chips: int) -> Dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d0.platform!r}); the "
                     "benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def enable_compile_cache(root: Path):
    """JAX's persistent cache at one fixed path inside the checkout, every
    program in it, so that only a cell's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Counts the backend compiles JAX reports while the process runs."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.seconds = 0, 0.0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.count += 1
                self.seconds += duration

        jax.monitoring.register_event_duration_secs_listener(listen)


class GcClock:
    """Records every collection of Python's garbage collector: (start,
    seconds, generation), on the loop's clock."""

    def __init__(self, clock):
        self.clock, self.pauses, self._t = clock, [], 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = self.clock()
        else:
            self.pauses.append((self._t, self.clock() - self._t,
                                info["generation"]))

    def close(self):
        gc.callbacks.remove(self._on)


def describe_stalls(steps, t0: float, gc_pauses, n: int = 3) -> List[str]:
    """The window's `n` longest steps with their work, the longest host
    time between two steps, and the collector's pauses, as log lines."""
    out = []
    for s in sorted(steps, key=lambda s: s.t0 - s.t1)[:n]:
        out.append(f"step of {(s.t1 - s.t0) * 1e3:.1f} ms at +{s.t0 - t0:.3f}"
                   f" s: {len(s.prefill)} prefill rows of "
                   f"{sum(k for k, _ in s.prefill)} tokens, "
                   f"{len(s.decode)} decode iterations of "
                   f"{max((len(i) for i in s.decode), default=0)} rows")
    between = [(b.t0 - a.t1, a.t1) for a, b in zip(steps, steps[1:])]
    if between:
        g, at = max(between)
        out.append(f"longest time between steps {g * 1e3:.1f} ms at "
                   f"+{at - t0:.3f} s")
    full = [d for _, d, gen in gc_pauses if gen == 2]
    out.append(f"garbage collections: {len(gc_pauses)}, "
               f"{sum(d for _, d, _ in gc_pauses) * 1e3:.1f} ms in all, "
               f"longest {max((d for _, d, _ in gc_pauses), default=0) * 1e3:.1f}"
               f" ms; full collections {len(full)}")
    return out


def warm_shapes(sched, budget: int, vocab: int):
    """Run one request per mixed-step chunk length the cell can use (the
    scheduler buckets chunks to 16 * 2^k, up to the chunk budget), each
    with two output tokens: the mixed program of that length and the
    decode chunk-scan compile here, not in the window."""
    L, n = int(sched.prefill_bucket), 0
    while True:
        L = min(L, budget)
        sched.submit([(7 * i + n) % vocab for i in range(L)], 2)
        sched.run()
        n += 1
        if L >= budget:
            return n
        L *= 2


class _Tracer:
    """Starts the profiler at the first step boundary past `t_start` and
    stops it at the first past `t_start + length`."""

    def __init__(self, loop: ServingLoop, directory: Path, t_start: float,
                 length: float):
        self.loop, self.dir, self.t_start = loop, directory, t_start
        self.length = length
        self.on = False
        self.first = 0
        self.steps: Tuple[int, int] = (0, 0)
        self.done = False

    def __call__(self, now: float):
        import jax
        if self.done:
            return
        if not self.on and now >= self.t_start:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self.on, self.first = True, len(self.loop.steps)
        elif self.on and now >= self.t_start + self.length:
            self.stop()

    def stop(self):
        import jax
        if self.on and not self.done:
            jax.profiler.stop_trace()
            self.steps = (self.first, len(self.loop.steps))
            self.done = True


def per_layer(cell: spec.Cell, layout, red, steps, device_kind: str
              ) -> Dict[str, Dict]:
    """Each per-layer metric of the cell, from its own reader; a reader
    that finds nothing to read leaves its metric out."""
    from bench.lib import counts
    ctx = SimpleNamespace(reduction=red, steps=steps, config=cell.config,
                          layout=layout,
                          server=cell.traffic["server"],
                          device_kind=device_kind,
                          peaks=counts.peaks(device_kind))
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(cell.root, m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def build_server(cell: spec.Cell, layout, seed: int,
                 overrides: Optional[Dict]):
    """The program's model and scheduler for the cell, by the cell's
    layout, with weights made from `seed`, and every step program the cell
    uses compiled."""
    from repro.models.model_zoo import build_model
    from repro.runtime.serve_lib import Scheduler

    cfg = layout.program_config(cell.config, **(overrides or {}))
    model = build_model(cfg)
    params = make_params(model, seed, layout.WEIGHT_RULES)
    server = cell.traffic["server"]
    sched = Scheduler(model, params, eos_id=None, temperature=0.0,
                      **{k: server[k] for k in SCHEDULER_OPTIONS
                         if k in server})
    n_warm = warm_shapes(sched, int(server["prefill_chunk_budget"]),
                         cfg.vocab_size)
    return cfg, params, sched, n_warm


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool,
             *, require_chip: bool = True, t_start: Optional[float] = None,
             overrides: Optional[Dict] = None, cache: bool = True
             ) -> Tuple[Dict, List[Tuple[str, float, float]]]:
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    cell = spec.load_cell(root, workload)
    if require_chip:
        device = device_check(cell.chips)
    else:
        d0 = jax.devices()[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(jax.devices())}
    log(f"device_kind={device['kind']} count={device['count']}")
    if cache:
        enable_compile_cache(root)
    clock = CompileClock()

    layout = spec.layout_module(root, cell.config)
    cfg, params, sched, n_warm = build_server(cell, layout, seed, overrides)
    server = cell.traffic["server"]
    traffic = Traffic(cell.traffic, seed, cfg.vocab_size, server["max_len"])
    loop = ServingLoop(sched, traffic)
    arr = cell.traffic["arrivals"]
    now = loop.clock()
    loop.start(now)
    if traffic.kind == "sessions":
        loop.run_until(float("inf"), stop=lambda: len(loop.recs) > 0 and all(
            r.tokens for r in loop.recs.values()))
    else:
        loop.run_until(now + float(arr["warm_s"]))
    setup_compiles = clock.count

    # -- window ---------------------------------------------------------------
    t0 = loop.clock()
    setup_s = t0 - t_start
    tracer = None
    if trace:
        length = max(TRACE_MIN_S, seconds / 4)
        lead = max(0.0, (seconds - length) / 2)
        tracer = _Tracer(loop, root / ".bench_trace" / workload, t0 + lead,
                         length)
    n_steps0 = len(loop.steps)
    gclock = GcClock(loop.clock)
    loop.run_until(t0 + seconds, between=tracer)
    t1 = loop.clock()
    gclock.close()
    window_steps = loop.steps[n_steps0:]
    if tracer is not None:
        tracer.stop()
    window_compiles = clock.count - setup_compiles
    in_window = [r for r in loop.recs.values() if t0 <= r.arrival < t1]
    loop.drain(lambda: all(r.tokens for r in in_window),
               t1 + float(cell.traffic.get("drain_s", 0.0)))
    drain_end = loop.clock()
    stats = sched.stats
    mem = jax.devices()[0].memory_stats() or {}
    # buffers, and the region the runtime reserves for the programs'
    # temporaries, which `peak_bytes_in_use` leaves out
    peak = (int(mem.get("peak_bytes_in_use", 0))
            + int(mem.get("peak_bytes_reserved", 0)))
    recs = list(loop.recs.values())
    summ = latency.summarize(recs, t0, t1, drain_end)
    late = sorted(loop.lateness) or [0.0]
    log(f"set-up {setup_s:.3f} s ({n_warm} shape warm-up requests, "
        f"{setup_compiles} backend compiles, {clock.seconds:.3f} s)")
    log(f"window {summ['window_s']:.3f} s, {len(loop.steps) - n_steps0} "
        f"steps; backend compiles inside the window: {window_compiles}")
    log(f"generator lateness: p50 {latency.percentile(late, 50) * 1e3:.3f} "
        f"ms, max {late[-1] * 1e3:.3f} ms over {len(loop.lateness)} submits")
    log(f"samples: ttft {summ['ttft_samples']} (without a token at the "
        f"drain's end: {summ['ttft_without_token']}; drain "
        f"{drain_end - t1:.3f} s), tpot {summ['tpot_samples']}, output "
        f"tokens {summ['output_tokens']}")
    log(f"tails (printed, not judged): ttft p90 {summ['ttft_p90_ms']:.3f} "
        f"ms over {summ['ttft_samples']} samples, tpot p90 "
        f"{summ['tpot_p90_ms']:.3f} ms over {summ['tpot_samples']}")
    for line in describe_stalls(window_steps, t0, gclock.pauses):
        log(line)
    log(f"device memory: peak in use {mem.get('peak_bytes_in_use')} B, "
        f"peak reserved {mem.get('peak_bytes_reserved')} B, limit "
        f"{mem.get('bytes_limit')} B")
    log("scheduler counters: " + json.dumps({k: stats[k] for k in (
        "steps", "model_steps", "evictions", "poisoned", "rejections",
        "queue_depth_p50", "queue_depth_p95")} | {
        "prefill_tokens_computed": sched.prefill_tokens_computed,
        "peak_pages_in_use": sched.peak_pages_in_use,
        "num_pages": sched.num_pages}))

    red = None
    if tracer is not None and tracer.done:
        from bench.lib import tracecut
        path = tracecut.find_trace(tracer.dir)
        if path is not None:
            red = tracecut.reduce(tracecut.load_xplane(path))
        traced = loop.steps[tracer.steps[0]:tracer.steps[1]]
    # -- check, once the program's state is freed ----------------------------
    loop.sched = None
    sched.cache = None
    del sched
    gc.collect()
    ref = spec.reference_module(root, cell.config)
    weights = layout.weight_views(params, cell.config)
    chk = cell.traffic["check"]
    sample = check.pick(recs, int(chk["requests"]), seed)
    t_chk = time.perf_counter()
    got = check.compare(ref.make(cell.config), weights, sample,
                        cfg.vocab_size, int(chk["tokens"]))
    log(f"check: reference over {got['requests_compared']} requests, "
        f"{got['tokens_compared']} served tokens, {time.perf_counter() - t_chk:.3f} s")
    limit = float(cell.limits.get("logit_gap", float("nan")))
    checks = [("logit_gap", got["logit_gap"], limit)]
    failed = got["out_of_vocab"]
    correct = (got["tokens_compared"] > 0 and failed == 0
               and got["logit_gap"] <= limit)

    attempted = len(in_window) if in_window else len(
        [r for r in recs if any(t0 < t <= t1 for t, _ in r.tokens)])
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(failed), "metrics": {},
              "device": dict(device, memory_peak_bytes=peak)}
    if not trace:
        values = {"setup_s": setup_s, **summ}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}
    else:
        if red is None:
            raise RuntimeError("the traced run produced no trace")
        result["metrics"] = per_layer(cell, layout, red, traced,
                                      device["kind"])
        result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    result["checks"] = {n: {"value": v if math.isfinite(v) else None,
                            "limit": lim} for n, v, lim in checks}
    return result, checks
