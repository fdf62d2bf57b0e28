"""The plain reference computes the program's architecture: with the PIM
linears off and float32 compute, the program's `forward_train` (float
attention) gives the reference's logits on the benchmark's weights."""
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tinyroot  # noqa: E402


def test_reference_matches_float_program():
    import jax
    import jax.numpy as jnp
    from bench.lib import check, spec
    from bench.lib.model import make_params
    from repro.models.model_zoo import build_model

    layout = spec.layout_module(ROOT, tinyroot.CONFIG)
    cfg = layout.program_config(tinyroot.CONFIG, pim_linears=False,
                                compute_dtype="float32")
    model = build_model(cfg)
    params = make_params(model, 2**32 + 3, layout.WEIGHT_RULES)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, 40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(model.forward_train)(
            params, {"tokens": jnp.asarray(toks)[None]})[0][0])
    ref = spec.reference_module(ROOT, tinyroot.CONFIG).make(tinyroot.CONFIG)
    weights = layout.weight_views(params, tinyroot.CONFIG)
    got = ref(weights, toks.tolist(), list(range(40)))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err
    # the widest gap of the reference's own argmax is 0
    assert check.widest_gap(got, got.argmax(-1)) == 0.0
