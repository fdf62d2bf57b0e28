"""The dense decoder's layout (`bench/layouts/dense_decoder.py`) builds
what the benchmark built before layouts existed: the same program config,
the same seeded weights bit for bit, the same views for the reference and
the same counts of the linears' work."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tinyroot  # noqa: E402
from bench.lib import spec  # noqa: E402

LAYOUT = spec.layout_module(ROOT, tinyroot.CONFIG)
INTERNLM2 = json.loads(
    (ROOT / "bench/configs/internlm2-1.8b.json").read_text())
SEED = 2**33 + 11


def test_program_config_is_the_old_one():
    from repro.configs.base import ModelConfig, PIMConfig
    assert LAYOUT.program_config(tinyroot.CONFIG) == ModelConfig(
        name="tiny", family="dense", num_layers=2, d_model=128,
        num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=256,
        activation="swiglu", norm="rmsnorm", tie_embeddings=False,
        rope_theta=10000.0, max_seq_len=512, block_pattern=("attn",),
        attn_impl="kernel", kv_bits=8, param_dtype="float32",
        compute_dtype="bfloat16", pim=PIMConfig(weight_bits=8, input_bits=8))
    assert LAYOUT.program_config(tinyroot.CONFIG, kv_bits=4).kv_bits == 4
    with pytest.raises(ValueError):
        LAYOUT.program_config(dict(tinyroot.CONFIG, hidden_act="gelu"))


@pytest.fixture(scope="module")
def params():
    from bench.lib.model import make_params
    from repro.models.model_zoo import build_model
    model = build_model(LAYOUT.program_config(tinyroot.CONFIG))
    return make_params(model, SEED, LAYOUT.WEIGHT_RULES)


def test_seeded_weights_are_the_old_ones(params):
    import jax
    leaves = [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(
        params)]
    # recorded with the benchmark's make_params before layouts existed
    assert len(leaves) == 12
    assert sum(float(x.sum()) for x in leaves) == 706.260267326405
    assert sum(float(np.square(x).sum()) for x in leaves) == (
        2729.497476133486)


def test_weight_views_are_the_old_ones(params):
    views = LAYOUT.weight_views(params, tinyroot.CONFIG)
    assert views["embed"] is params["embed"]["table"]
    assert views["head"] is params["unembed"]["table"]
    assert views["final_norm"] is params["final_norm"]["scale"]
    blocks = params["blocks"][0]
    for i in range(tinyroot.CONFIG["num_hidden_layers"]):
        # the removed `layer_weights(params, i)`, as it read the tree
        old = {"norm1": blocks["norm1"]["scale"][i],
               "norm2": blocks["norm2"]["scale"][i],
               **{k: blocks["attn"][k]["w"][i]
                  for k in ("wq", "wk", "wv", "wo")},
               **{k: blocks["mlp"][k]["w"][i]
                  for k in ("w_gate", "w_in", "w_out")}}
        got = views["layer"](i)
        assert set(got) == set(old)
        for k in old:
            np.testing.assert_array_equal(got[k], old[k])


@pytest.mark.parametrize("tokens", [1, 8, 259])
def test_linear_work_internlm2(tokens):
    # the weights `test_bench_counts.py::test_model_ops_internlm2` pins
    w = 1_509_949_440
    assert LAYOUT.linear_work(INTERNLM2, tokens) == (2 * w * tokens, w)
    assert LAYOUT.attention_layers(INTERNLM2) == 24
