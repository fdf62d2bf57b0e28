"""Reduce a profiler trace to the benchmark's device numbers.

A trace is read into `Event`s (plane, line, name, start, duration, in
nanoseconds on the trace's one clock) from the JAX profiler's `.xplane.pb`.
From those:

- device busy: the union of the intervals of device operations (control
  flow such as a `while` op left out: its event spans the ops it runs),
  inside the traced window (the first to the last harness span);
- idle share: 1 - busy / window;
- time by kernel: summed device durations of the operations whose name
  contains the kernel's name, or whose scope (the `op_name` of its HLO
  instruction, the JAX name stack such as
  `jit(step)/.../jit(pim_decode_pallas)/jit(_take)/gather`) lies inside the
  kernel's jitted wrapper, `jit(<kernel>)`: the custom call and the XLA
  ops the wrapper runs around it;
- idle gaps, each named by the harness span (`submit`, `step`, `deliver`)
  that holds its midpoint, or `none`;
- host time per step: the part of each `step` span in which no device
  operation runs.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_SPANS = ("submit", "step", "deliver")
# control flow that holds other ops: its event spans theirs
CONTROL_FLOW = re.compile(r"^%?(while|conditional|call)[.\d]* = ")
# a device plane's line that carries one event per XLA operation, and the
# line of the programs (XLA modules) that hold them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float        # ns
    dur: float          # ns
    scope: str = ""     # a device op's name stack (HLO `op_name`)

    @property
    def end(self) -> float:
        return self.start + self.dur


def load_xplane(path) -> List[Event]:
    """The trace's events; a device op's `scope` is the name stack of its
    HLO instruction (`op_name` metadata, from the HLO the trace holds)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    scopes = hlo_scopes(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        modules = []
        for line in plane.lines:
            if device and line.name == MODULES_LINE:
                modules += [(float(e.start_ns), float(e.start_ns)
                             + float(e.duration_ns), e.name)
                            for e in line.events]
        modules.sort()
        starts = [m[0] for m in modules]
        for line in plane.lines:
            ops = device and line.name == OPS_LINE
            for e in line.events:
                start = float(e.start_ns)
                scope = ""
                if ops:
                    i = bisect.bisect_right(starts, start) - 1
                    module = (modules[i][2] if i >= 0
                              and start < modules[i][1] else "")
                    scope = _scope(scopes, module,
                                   op_name(e.name).lstrip("%"))
                out.append(Event(plane.name, line.name, e.name, start,
                                 float(e.duration_ns), scope))
    return out


def _scope(scopes: Dict[str, Dict[str, str]], module: str,
           instr: str) -> str:
    """`instr`'s name stack in `module`; where the module is not known by
    that name, every module's instruction of that name."""
    if module in scopes:
        return scopes[module].get(instr, "")
    return " ".join(sorted({m[instr] for m in scopes.values()
                            if instr in m}))


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes):
    """(field number, value) of a protobuf message's wire format: an int
    for varints, bytes for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unknown protobuf wire type {kind}")
        yield key >> 3, v


def _first(b: bytes, field: int, default=b""):
    return next((v for f, v in _fields(b) if f == field), default)


def hlo_scopes(path) -> Dict[str, Dict[str, str]]:
    """The `op_name` of every HLO instruction, by module (`name(id)`) and
    instruction name, from the HLO protos of the trace's `/host:metadata`
    plane.  XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry:
    value 2); XEventMetadata.name 2, .stats 5; XStat.bytes_value 6;
    HloProto.hlo_module 1; HloModuleProto.computations 3;
    HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .metadata 7; OpMetadata.op_name 2."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(Path(path).read_bytes()):
        if f != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            meta = _first(entry, 2)
            names: Dict[str, str] = {}
            for f3, stat in _fields(meta):
                if f3 != 5:
                    continue
                proto = _first(stat, 6, None)
                if not isinstance(proto, bytes):
                    continue
                module = _first(proto, 1)
                for f4, comp in _fields(module):
                    if f4 != 3:
                        continue
                    for f5, ins in _fields(comp):
                        if f5 == 2:
                            names[_first(ins, 1).decode()] = _first(
                                _first(ins, 7), 2).decode()
            if names:
                out[_first(meta, 2).decode()] = names
    return out


def find_trace(directory) -> Optional[Path]:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    return found[-1] if found else None


def is_device_op(e: Event) -> bool:
    """An operation on a device; control flow, whose event spans the ops it
    runs, is not one."""
    return (e.plane.startswith("/device:") and "CPU" not in e.plane
            and e.line == OPS_LINE and e.dur > 0
            and not CONTROL_FLOW.match(e.name))


def op_name(e) -> str:
    """An op's name (an `Event`'s, or a name string) without its HLO text:
    `%fusion.12 = f32[..] ...` -> `%fusion.12`."""
    return getattr(e, "name", e).split(" = ", 1)[0]


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    return sum(e - s for s, e in clip(intervals, lo, hi))


@dataclasses.dataclass
class Reduction:
    window: Tuple[float, float]                 # ns
    n_devices: int
    busy: List[List[Tuple[float, float]]]       # merged, per device
    ops: List[Event]                            # device ops in the window
    spans: List[Event]                          # harness spans, by start

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices traced."""
        tot = sum(sum(e - s for s, e in b) for b in self.busy)
        return tot * 1e-9 / max(self.n_devices, 1)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_ops(self, kernel: str) -> List[Event]:
        """The ops of `kernel`: by name, or run inside `jit(<kernel>)`."""
        wrapper = f"jit({kernel})"
        return [e for e in self.ops
                if kernel in e.name or wrapper in e.scope]

    def kernel_seconds(self, kernel: str) -> float:
        return sum(e.dur for e in self.kernel_ops(kernel)) * 1e-9

    def steps(self) -> List[Event]:
        return [s for s in self.spans if s.name == "step"]

    def host_ms_per_step(self) -> Optional[float]:
        steps = self.steps()
        if not steps or not self.busy:
            return None
        idle = sum((s.dur - overlap(self.busy[0], s.start, s.end))
                   for s in steps)
        return idle * 1e-6 / len(steps)

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        by: Dict[str, float] = {}
        for e in self.ops:
            by[op_name(e)] = by.get(op_name(e), 0.0) + e.dur
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v * 1e-9) for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The `n` longest gaps on the first device, each named by the
        harness span holding its midpoint."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy[0] if self.busy else []:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (s + e)
            name = next((sp.name for sp in self.spans
                         if sp.start <= mid <= sp.end), "none")
            out.append((name, (e - s) * 1e-9))
        return out


def reduce(events: Sequence[Event]) -> Reduction:
    spans = sorted((e for e in events if e.name in HOST_SPANS
                    and not e.plane.startswith("/device:")),
                   key=lambda e: e.start)
    if not spans:
        raise ValueError("the trace holds no harness span")
    lo, hi = spans[0].start, max(s.end for s in spans)
    planes = sorted({e.plane for e in events if is_device_op(e)})
    ops = [e for e in events if is_device_op(e) and e.end > lo
           and e.start < hi]
    busy = [merge(clip([(e.start, e.end) for e in ops if e.plane == p],
                       lo, hi)) for p in planes]
    return Reduction((lo, hi), len(planes), busy, ops, spans)
