"""The MoE layer's load counts in a run's profiler trace.

A MoE stack's `Scheduler` marks each step with a host span `moe.load`
after the step's readback (`runtime/serve_lib.Scheduler._mark_load`).  Its
two arguments are what the step program counted, summed over its forwards
and MoE layers: `assignments`, the routed (token, expert) assignments of
valid tokens that reached the experts this chip holds, and `active`, the
(forward, layer, held expert) triples that received at least one.

The spans come from the trace the run wrote, as the program's other spans
do (`progspans`): the newest trace under `<root>/.bench_trace/`, taken
only where its first harness span starts at the reduction's window, and
only the spans inside its traced `step` spans.  Where there is no such
span, as in a dense stack's trace, the reader reads None.
"""
from __future__ import annotations

import bisect
import functools
from pathlib import Path
from typing import List, Optional, Tuple

from bench.lib import progspans, tracecut

SPAN = "moe.load"


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Tuple[Tuple[float, int, int], ...]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SPAN:
                    args = dict(e.stats)
                    out.append((float(e.start_ns), int(args["assignments"]),
                                int(args["active"])))
    return tuple(sorted(out))


def load_spans(root) -> Tuple[Tuple[float, int, int], ...]:
    """(start ns, assignments, active) of every `moe.load` span of the
    newest trace under `<root>/.bench_trace/`, by start."""
    path = progspans.newest_trace(root)
    if path is None:
        return ()
    return _load(str(path), path.stat().st_mtime)


def step_loads(ctx, metric_file) -> Optional[List[Tuple[int, int]]]:
    """(assignments, active) of each `moe.load` span inside this run's
    traced `step` spans; None without one, or where the newest trace is
    not the one `ctx.reduction` was made from."""
    red = ctx.reduction
    if red is None:
        return None
    root = Path(metric_file).parents[2]
    harness = [e for e in progspans.host_spans(root)
               if e.name in tracecut.HOST_SPANS]
    if not harness or harness[0].start != red.window[0]:
        return None
    steps = red.steps()
    starts = [s.start for s in steps]
    out = []
    for start, assignments, active in load_spans(root):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start <= steps[i].end:
            out.append((assignments, active))
    return out or None
