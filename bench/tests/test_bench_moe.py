"""The DeepSeekMoE architecture's plain reference (`moe_decoder`) against
the program at a tiny size on the CPU, on the benchmark's own weights:
the float program gives the reference's logits; the served int8 program,
prefilled then decoded through the paged kernel path, stays within a
stated distance of them, and the int4 control does not."""
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tinymoe  # noqa: E402
from bench.lib import check, spec  # noqa: E402

SEED = 2**33 + 5
LAYOUT = spec.layout_module(ROOT, tinymoe.CONFIG)


def _model(cfg_file, **overrides):
    from bench.lib.model import make_params
    from repro.models.model_zoo import build_model
    model = build_model(LAYOUT.program_config(cfg_file, **overrides))
    return model, make_params(model, SEED, LAYOUT.WEIGHT_RULES)


@pytest.mark.parametrize("norm", [False, True])
def test_reference_matches_float_program(norm):
    """PIM linears off, float32 compute: the program's `forward_train`
    (float attention, dropless routing over the held experts) gives the
    reference's logits to 1e-4, with the gates renormalised or not."""
    import jax
    import jax.numpy as jnp
    c = dict(tinymoe.CONFIG, norm_topk_prob=norm)
    model, params = _model(c, pim_linears=False, compute_dtype="float32")
    toks = np.random.default_rng(0).integers(0, c["vocab_size"], 40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(model.forward_train)(
            params, {"tokens": jnp.asarray(toks)[None]})[0][0])
    ref = spec.reference_module(ROOT, c).make(c)
    got = ref(LAYOUT.weight_views(params, c), toks.tolist(), list(range(40)))
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-4, err
    assert check.widest_gap(got, got.argmax(-1)) == 0.0


def _served_logits(overrides):
    """Prefill a 24-token prompt into the paged pool in one chunk, then
    decode 8 tokens one at a time (each the reference's best), on the
    kernel path: the logits of the last prompt position and each decode."""
    import jax
    import jax.numpy as jnp
    c = tinymoe.CONFIG
    model, params = _model(c, **overrides)
    ref = spec.reference_module(ROOT, c).make(c)
    views = LAYOUT.weight_views(params, c)
    prompt = np.random.default_rng(1).integers(0, c["vocab_size"], 24)
    seq = prompt.tolist()
    cache = model.init_cache(1, 48, ragged=True, page_size=16, num_pages=4)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    step = jax.jit(lambda p, cache, t, off, n: model.forward_serve(
        p, {"tokens": t}, cache, off, seq_lens=n, pages=table)[:2])
    got = []
    logits, cache = step(params, cache, jnp.asarray(prompt)[None],
                         jnp.zeros((1,), jnp.int32), jnp.asarray([24]))
    got.append(np.asarray(logits[0]))
    for _ in range(8):
        nxt = int(ref(views, seq, [len(seq) - 1])[0].argmax())
        logits, cache = step(params, cache, jnp.asarray([[nxt]]),
                             jnp.asarray([len(seq)]), jnp.asarray([1]))
        seq.append(nxt)
        got.append(np.asarray(logits[0]))
    want = ref(views, seq, list(range(23, len(seq))))
    return np.stack(got), want


def test_paged_kernel_path_follows_the_reference():
    """Logits, not tokens, position by position: the relative L2 distance
    of the served logits from the reference's, at its widest over the 9
    positions.  On this tiny model the int8 program reads 0.08-0.10 over
    three seeds (int8 weights, inputs and KV, the LUT softmax, routing
    flips near ties) and the int4 control (4-bit weights, inputs and KV,
    one step below the int8 the configuration states) 1.2-1.4; 0.3 lies
    between, with room on both sides."""
    from repro.configs.base import PIMConfig

    def distance(overrides):
        got, want = _served_logits(overrides)
        return max(np.linalg.norm(g - w) / np.linalg.norm(w)
                   for g, w in zip(got, want))

    assert distance({}) < 0.3
    assert distance({"pim": PIMConfig(weight_bits=4, input_bits=4),
                     "kv_bits": 4}) > 0.3


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinymoe.make(tmp_path_factory.mktemp("bench") / "root")


@pytest.mark.parametrize("control", [False, True])
def test_served_cell_is_correct_and_the_control_is_not(root, control):
    """The whole cell through the paged `Scheduler` (mixed prefill chunks,
    decode chunk-scans), checked as the chip run is: the widest gap of a
    served token's reference logit below the reference's best, against
    the cell's limit."""
    from bench.lib.harness import run_cell
    from repro.configs.base import PIMConfig
    over = ({"pim": PIMConfig(weight_bits=4, input_bits=4), "kv_bits": 4}
            if control else None)
    result, checks = run_cell(root, tinymoe.CELL, SEED, 2.0, False,
                              require_chip=False, cache=False,
                              overrides=over)
    (_, gap, limit), = checks
    assert result["correct"] is (not control), gap
    assert (gap > limit) is control
    assert set(result["metrics"]) == {"setup_s", "tpot_p50_ms",
                                      "output_tokens_per_s"}
