"""Find a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by name:

    configs[].file                      the configuration's sizes (JSON)
    bench/traffic/<traffic>.json        the traffic mix and its server options
    bench/metrics/<metric>.py           a per-layer metric's reader
    bench/limits/<workload>.json        the limit that decides `correct`
    bench/reference/<reference>.py      the plain reference a config names
    bench/layouts/<reference>.py        how that architecture maps onto the
                                        program: its config, weight rules,
                                        the reference's weight views and
                                        the work of its linears

A new cell, configuration, mix or metric is new files and new entries; no
existing file changes.  A configuration of a new architecture is its
config, reference, layout and limits, and the cell's traffic if new.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List


@dataclasses.dataclass
class Cell:
    root: Path
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_benchmark(root) -> Dict[str, Any]:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root, workload: str, bench: Dict[str, Any] = None) -> Cell:
    """The cell `workload` of the benchmark at `root`; KeyError when the
    name or one of its files is unknown."""
    root = Path(root)
    bench = bench if bench is not None else load_benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(by_name)}")
    w = by_name[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())
    return Cell(root, w, config, traffic, limits,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                [m for m in bench["per_layer"] if _applies(m, workload)])


def load_module(path: Path, name: str):
    """Import a benchmark file by path (metric names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(root, name: str):
    """The `read(ctx)` function of per-layer metric `name`."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    return load_module(path, f"bench_metric_{name.replace('.', '_')}").read


def reference_module(root, config: Dict[str, Any]):
    name = config["reference"]
    return load_module(Path(root) / "bench" / "reference" / f"{name}.py",
                       f"bench_reference_{name}")


def layout_module(root, config: Dict[str, Any]):
    """The layout of the architecture `config` names by its `reference`:
    the one benchmark file per architecture that knows the program's
    config and parameter tree."""
    name = config["reference"]
    return load_module(Path(root) / "bench" / "layouts" / f"{name}.py",
                       f"bench_layout_{name}")
