"""The program's own spans and name scopes in a run's profiler trace.

The program marks the phases of each `Scheduler.step` with host spans
(`sched.admit`, `sched.plan`, `sched.dispatch`, `sched.readback`,
`sched.commit`; `runtime/serve_lib.py`), and its linears' and the KV pool
relayout's device ops carry the name scopes `pim_linear/` and
`kv_relayout/` in their HLO `op_name` (`core/pim.py`, `kernels/ops.py`).

A reader is handed the harness's reduction, whose spans are the harness's
own.  So the program spans come from the trace that run wrote: the newest
`*.xplane.pb` under `<root>/.bench_trace/`, taken only where its first
harness span starts at the reduction's window.  `<root>` is found from the
reader's own file as loaded (`<root>/bench/metrics/<name>.py`, links not
resolved).  Where the trace holds no program span, or no op in a scope, the
readers read None.
"""
from __future__ import annotations

import bisect
import functools
from pathlib import Path
from typing import List, Optional, Sequence, Tuple

from bench.lib import tracecut

PREFIX = "sched."
PREP = ("sched.admit", "sched.plan", "sched.dispatch")
COMMIT = ("sched.readback", "sched.commit")


def newest_trace(root) -> Optional[Path]:
    found = list((Path(root) / ".bench_trace").rglob("*.xplane.pb"))
    return max(found, key=lambda p: p.stat().st_mtime) if found else None


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Tuple[tracecut.Event, ...]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in tracecut.HOST_SPANS or e.name.startswith(PREFIX):
                    out.append(tracecut.Event(plane.name, line.name, e.name,
                                              float(e.start_ns),
                                              float(e.duration_ns)))
    return tuple(sorted(out, key=lambda e: e.start))


def host_spans(root) -> Sequence[tracecut.Event]:
    """The harness's and the program's host spans of the newest trace
    under `<root>/.bench_trace/`, by start (read once per file)."""
    path = newest_trace(root)
    if path is None:
        return ()
    return _load(str(path), path.stat().st_mtime)


def program_spans(ctx, metric_file) -> Optional[List[tracecut.Event]]:
    """The `sched.*` spans of this run's trace that lie inside its traced
    `step` spans; None without them, or where the newest trace is not the
    one `ctx.reduction` was made from."""
    red = ctx.reduction
    if red is None:
        return None
    spans = host_spans(Path(metric_file).parents[2])
    harness = [e for e in spans if e.name in tracecut.HOST_SPANS]
    if not harness or harness[0].start != red.window[0]:
        return None
    steps = red.steps()
    starts = [s.start for s in steps]
    out = []
    for e in spans:
        if not e.name.startswith(PREFIX):
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= steps[i].end:
            out.append(e)
    return out or None


def idle_ms_per_step(ctx, metric_file, names: Sequence[str]
                     ) -> Optional[float]:
    """Per traced step, the time inside the program spans named `names`
    (their union) in which no op runs on the first device, in ms."""
    spans = program_spans(ctx, metric_file)
    red = ctx.reduction
    if not spans or not red.busy:
        return None
    mine = tracecut.merge((e.start, e.end) for e in spans if e.name in names)
    if not mine:
        return None
    idle = sum(e - s - tracecut.overlap(red.busy[0], s, e) for s, e in mine)
    return idle * 1e-6 / len(red.steps())


def scoped_seconds(red, scope: str) -> float:
    """Device seconds of the traced ops whose name stack holds
    `<scope>/`."""
    tag = scope + "/"
    return sum(e.dur for e in red.ops if tag in e.scope) * 1e-9
