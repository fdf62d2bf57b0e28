#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest arrival
rate the server sustains without a growing backlog.

    python3 bench/sweep.py --workload <name> --rates 0.5,1,1.5 [--seed n]
        [--warm 10] [--seconds 30] [--slots N]

One process sets the cell up once, then for each rate offers the cell's
traffic at that rate for `--warm` seconds and measures `--seconds` more:
the requests waiting or prefilling at the start and the end of the measured
part, TTFT p50/p90 of its arrivals, and its output tokens per second.  The
backlog grows where the waiting count climbs across the measured part.
Rates go up; between two, the server drains for at most `--drain` seconds.
The knee it finds is written into the cell's traffic file by hand, as a
number; the benchmark never searches.  `--slots` tries another slot count
than the traffic file states.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def waiting(sched) -> int:
    return len(sched.queue) + int(sched.prefilling.sum())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--warm", type=float, default=10.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=90.0)
    ap.add_argument("--slots", type=int, default=0)
    args = ap.parse_args(argv)

    from bench.lib import harness, latency, spec
    from bench.lib.loop import ServingLoop
    from bench.lib.traffic import Traffic

    cell = spec.load_cell(ROOT, args.workload)
    if args.slots:
        cell.traffic["server"]["max_batch_slots"] = args.slots
    harness.device_check(cell.chips)
    harness.enable_compile_cache(ROOT)
    cfg, _, sched, _ = harness.build_server(
        cell, spec.layout_module(ROOT, cell.config), args.seed, None)
    server = cell.traffic["server"]
    for rate in (float(r) for r in args.rates.split(",")):
        mix = json.loads(json.dumps(cell.traffic))
        mix["arrivals"]["rate_per_s"] = rate
        loop = ServingLoop(sched, Traffic(mix, args.seed, cfg.vocab_size,
                                          server["max_len"]))
        t = loop.clock()
        loop.start(t)
        loop.run_until(t + args.warm)
        w0, t0 = waiting(sched), loop.clock()
        loop.run_until(t0 + args.seconds)
        w1, t1 = waiting(sched), loop.clock()
        loop.drain(lambda: False, t1 + args.drain)
        s = latency.summarize(list(loop.recs.values()), t0, t1, loop.clock())
        print(json.dumps({"rate_per_s": rate,
                          "slots": server["max_batch_slots"],
                          "waiting_start": w0,
                          "waiting_end": w1, "arrivals": s["ttft_samples"],
                          "ttft_p50_ms": s["ttft_p50_ms"],
                          "ttft_p90_ms": s["ttft_p90_ms"],
                          "tpot_p50_ms": s["tpot_p50_ms"],
                          "tpot_p90_ms": s["tpot_p90_ms"],
                          "output_tokens_per_s": s["output_tokens_per_s"],
                          "steps": len(loop.steps)}), flush=True)


if __name__ == "__main__":
    main()
