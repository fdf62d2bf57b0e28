"""A benchmark root at a size the CPU runs, with one tiny configuration of
the DeepSeekMoE architecture (`moe_decoder`) beside the dense ones of
`tinyroot`: a dense layer, then two MoE layers that hold 4 of a router's
16 experts (from expert 4 on), top-6, gates not renormalised, 2 shared
experts, MHA attention.  Its cell `tiny-moe.sessions` runs the sessions
mix and reports the MoE metrics."""
from __future__ import annotations

import json
from pathlib import Path

import tinyroot

CONFIG = dict(
    tinyroot.CONFIG, name="tiny-moe", reference="moe_decoder",
    intermediate_size=320, moe_intermediate_size=64, num_hidden_layers=3,
    num_key_value_heads=4, first_k_dense_replace=1, moe_layer_freq=1,
    n_routed_experts=4, router_experts=16, first_held_expert=4,
    n_shared_experts=2, num_experts_per_tok=6, norm_topk_prob=False,
    scoring_func="softmax", rms_norm_eps=1e-6)
CELL = "tiny-moe.sessions"
# on the CPU a sound run reads a widest gap of 0.019-0.058 and the int4
# control 0.87 (seeds 2**33 + 5, 7): routing flips near ties widen the
# sound gaps past the dense decoder's
LIMIT = {"logit_gap": 0.25}


def make(root: Path) -> Path:
    root = tinyroot.make(root)
    (root / "bench" / "configs" / "tiny-moe.json").write_text(
        json.dumps(CONFIG))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps(LIMIT))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "bench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-moe",
                               "traffic": "tiny-sessions", "chips": 1,
                               "why": "test"})
    for m in bench["per_layer"]:
        if m["name"].startswith("moe_"):
            m["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
