#!/usr/bin/env python3
"""Readings that set a cell's `correct` limit, on the chip.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10
        [--control]

Runs the cell's whole run (set-up, a window of `--seconds`, drain, check)
once per seed in one process and prints the widest logit gap each reads.
Plain, these are the sound program's readings (the lower end).  With
`--control` the program runs its own lower-precision path instead, the
control that has to come out not correct (the upper end): PIM linears at
4-bit weights and inputs and 4-bit KV, one step below the int8 the
configuration states.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def control_overrides():
    from repro.configs.base import PIMConfig
    return {"pim": PIMConfig(weight_bits=4, input_bits=4), "kv_bits": 4}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from bench.lib.harness import run_cell
    over = control_overrides() if args.control else None
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks = run_cell(ROOT, args.workload, seed, args.seconds,
                                  False, overrides=over)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": result["correct"],
                          "checks": result["checks"],
                          "metrics": result["metrics"]}), flush=True)


if __name__ == "__main__":
    main()
