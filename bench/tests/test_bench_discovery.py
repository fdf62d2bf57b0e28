"""BENCHMARK.json's shape, and a cell, configuration, mix, metric or
architecture added as new files only: the harness finds each by its
name."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
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


TINY_MOE = dict(
    {k: v for k, v in tinyroot.CONFIG.items() if k != "intermediate_size"},
    name="tiny-moe", reference="tiny_moe", num_hidden_layers=3,
    num_key_value_heads=4, intermediate_size=64, moe_intermediate_size=64,
    first_k_dense_replace=1, n_routed_experts=4, n_shared_experts=1,
    num_experts_per_tok=2, norm_topk_prob=True)


def test_new_architecture_is_new_files_only(tmp_path):
    """A MoE architecture (a dense layer, then two layers of 4 routed
    experts, top-2, beside a shared one) is its layout, its reference and
    a config that names them; its `correct` check is not run here."""
    import jax
    from bench.lib.model import _leaf_name, make_params, seed_key
    from repro.models.model_zoo import build_model

    root = tinyroot.make(tmp_path / "root")
    for sub in ("layouts", "reference"):      # private copies to add to
        (root / "bench" / sub).unlink()
        shutil.copytree(ROOT / "bench" / sub, root / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    data = HERE / "data"
    shutil.copy(data / "tiny_moe_layout.py",
                root / "bench" / "layouts" / "tiny_moe.py")
    shutil.copy(data / "tiny_moe_reference.py",
                root / "bench" / "reference" / "tiny_moe.py")
    (root / "bench" / "configs" / "tiny-moe.json").write_text(
        json.dumps(TINY_MOE))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for p, data_ in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data_, p

    entry = [c for c in spec.load_benchmark(root)["configs"]
             if c["name"] == "tiny-moe"][0]
    cfg = json.loads((root / entry["file"]).read_text())
    layout = spec.layout_module(root, cfg)
    assert Path(layout.__file__) == root / "bench/layouts/tiny_moe.py"
    with pytest.raises(NotImplementedError):
        spec.reference_module(root, cfg).make(cfg)
    with pytest.raises(ValueError):
        layout.program_config(dict(cfg, intermediate_size=128))

    model = build_model(layout.program_config(cfg))
    with pytest.raises(ValueError, match="no rule for weight leaf"):
        make_params(model, 7)
    params = make_params(model, 7, layout.WEIGHT_RULES)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    names = [_leaf_name(p) for p, _ in flat]
    for want in ("blocks/0/moe/router", "blocks/0/moe/experts/w_gate",
                 "blocks/0/moe/experts/w_out", "blocks/0/moe/shared/w_in",
                 "dense_prefix/0/mlp/w_gate/w", "dense_prefix/0/attn/wq/w"):
        assert want in names, (want, names)
    # the router is drawn by the layout's rule from its own fold of the
    # seed, compiled as make_params compiles it
    i = names.index("blocks/0/moe/router")
    rule = jax.jit(lambda k: layout.WEIGHT_RULES["router"](k, (2, 128, 4)))
    np.testing.assert_array_equal(flat[i][1],
                                  rule(jax.random.fold_in(seed_key(7), i)))

    views = layout.weight_views(params, cfg)
    dense, moe = views["layer"](0), views["layer"](1)
    assert "router" not in dense
    np.testing.assert_array_equal(
        dense["w_gate"], params["dense_prefix"][0]["mlp"]["w_gate"]["w"])
    blocks = params["blocks"][0]["moe"]
    np.testing.assert_array_equal(moe["router"], blocks["router"][0])
    np.testing.assert_array_equal(views["layer"](2)["router"],
                                  blocks["router"][1])
    assert moe["experts"]["w_gate"].shape == (4, 128, 64)
    assert moe["experts"]["w_out"].shape == (4, 64, 128)
    assert moe["shared"]["w_in"].shape == (1, 128, 64)
    # per token: 3 layers of attention (128 * 32 * 16 weights), the dense
    # layer (3 * 128 * 64) and 2 MoE layers of 2 routed + 1 shared experts
    per_token = 3 * 65536 + 24576 + 2 * 3 * 24576
    assert layout.linear_work(cfg, 5) == (2 * per_token * 5, per_token)
    assert layout.attention_layers(cfg) == 3


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
