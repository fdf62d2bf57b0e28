"""BENCHMARK.json's shape, and a cell, configuration, mix or metric added
as new files only: the harness finds each by its name."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tinyroot  # noqa: E402
from bench.lib import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = spec.load_cell(ROOT, w["name"])
        assert "logit_gap" in cell.limits
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        for w in m["workloads"]:
            assert w in cells
            assert m["moves"] in {x["name"] for x in
                                  spec.load_cell(ROOT, w).end_to_end}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tinyroot.make(tmp_path / "root")
    metrics = root / "bench" / "metrics"
    metrics.unlink()                     # a private copy to add a file to
    shutil.copytree(ROOT / "bench" / "metrics", metrics)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    cfg = dict(tinyroot.CONFIG, name="tiny-wide", hidden_size=256)
    (root / "bench" / "configs" / "tiny-wide.json").write_text(
        json.dumps(cfg))
    mix = dict(tinyroot.MIXES["tiny-chat"],
               arrivals={"kind": "closed", "clients": 3, "warm_s": 0.5})
    (root / "bench" / "traffic" / "tiny-agents.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / "tiny-wide.agents.json").write_text(
        json.dumps({"logit_gap": 1.0}))
    (metrics / "steps_traced.py").write_text(
        "def read(ctx):\n    return len(ctx.steps) if ctx.steps else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide.agents",
                               "config": "tiny-wide",
                               "traffic": "tiny-agents", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps",
                               "better": "higher", "source": "device_trace",
                               "layer": "scheduler", "moves": "tpot_p50_ms",
                               "workloads": ["tiny-wide.agents"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell(root, "tiny-wide.agents")
    assert cell.config["hidden_size"] == 256
    assert cell.traffic["arrivals"]["kind"] == "closed"
    assert cell.limits == {"logit_gap": 1.0}
    assert "steps_traced" in [m["name"] for m in cell.per_layer]
    read = spec.metric_reader(root, "steps_traced")
    assert read(type("Ctx", (), {"steps": [1, 2]})) == 2
    # the older cells see none of it, and no file that was there changed
    assert "steps_traced" not in [
        m["name"] for m in spec.load_cell(root, "tiny.chat").per_layer]
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p
    with pytest.raises(KeyError):
        spec.load_cell(root, "tiny-wide.unknown")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
