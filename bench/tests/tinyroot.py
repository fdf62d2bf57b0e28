"""A benchmark root at a size the CPU runs: one tiny configuration of the
same dense decoder and two small mixes, beside the real metric readers,
references and layouts.  Tests run the whole harness on it."""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "source": "test", "reference": "dense_decoder",
    "hidden_act": "silu", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "vocab_size": 256,
    "max_position_embeddings": 512, "rope_theta": 10000, "bias": False,
    "tie_word_embeddings": False,
    "program": {"attn_impl": "kernel", "kv_bits": 8,
                "param_dtype": "float32", "compute_dtype": "bfloat16",
                "pim": {"weight_bits": 8, "input_bits": 8}},
}
SERVER = {"max_batch_slots": 4, "max_len": 96, "page_size": 16,
          "num_pages": 25, "mixed_steps": True, "prefill_chunk_budget": 32}
MIXES = {
    "tiny-chat": {
        "arrivals": {"kind": "poisson", "rate_per_s": 4.0, "warm_s": 0.5},
        "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                       "min": 8, "max": 48},
        "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                       "min": 2, "max": 12},
        "server": SERVER, "drain_s": 20.0,
        "check": {"requests": 3, "tokens": 24}},
    "tiny-sessions": {
        "arrivals": {"kind": "sessions", "sessions": 2},
        "prompt_len": {"dist": "uniform", "min": 24, "max": 40},
        "output_len": {"dist": "fixed", "value": 40},
        "server": SERVER, "drain_s": 0.0,
        "check": {"requests": 2, "tokens": 24}},
}
# on the CPU a sound tiny run reads a widest gap of 0.008-0.016 and the
# int4 control 0.50-0.87 (seeds 1-3)
LIMIT = {"logit_gap": 0.1}


def make(root: Path) -> Path:
    root = Path(root)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "limits").mkdir()
    for sub in ("metrics", "reference", "layouts"):
        (root / "bench" / sub).symlink_to(BENCH / sub)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    cells = []
    for mix, body in MIXES.items():
        (root / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(body))
        name = f"tiny.{mix.split('-', 1)[1]}"
        (root / "bench" / "limits" / f"{name}.json").write_text(
            json.dumps(LIMIT))
        cells.append({"name": name, "config": "tiny", "traffic": mix,
                      "chips": 1, "why": "test"})
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    bench = dict(real, configs=[{"name": "tiny", "source": "test",
                                 "file": "bench/configs/tiny.json",
                                 "reduced": [], "why": "test"}],
                 workloads=cells)
    for group in ("end_to_end", "per_layer"):
        # metrics of the real chat cell alone go to the tiny chat cell
        bench[group] = [
            dict({k: v for k, v in m.items() if k != "workloads"},
                 **({"workloads": ["tiny.chat"]}
                    if len(m.get("workloads", [0, 0])) == 1 else {}))
            for m in real[group]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
