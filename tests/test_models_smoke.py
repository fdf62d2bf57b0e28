"""Per-architecture smoke tests: reduced configs, one train fwd + serve cycle.

Every assigned arch instantiates a REDUCED config of the same family and runs
a forward/train step on CPU asserting output shapes and no NaNs, plus a
prefill/decode consistency check through the PIM serve path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models.model_zoo import build_model


def _batch(key, cfg, B, S):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            key, (B, cfg.encoder_seq_len, cfg.d_model))
    if cfg.num_image_patches:
        batch["image_embeds"] = jax.random.normal(
            key, (B, cfg.num_image_patches, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_train_forward_shapes_and_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    B, S = 2, 16
    batch = _batch(key, cfg, B, S)
    logits, aux = jax.jit(model.forward_train)(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert bool(jnp.isfinite(aux))
    if cfg.moe.num_experts:
        assert float(aux) > 0.0  # load-balance loss is active


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_loss_and_grads_finite(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init(key)
    batch = _batch(key, cfg, 2, 8)
    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, batch)
    assert bool(jnp.isfinite(loss))
    finite = jax.tree.map(
        lambda g: bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), grads)
    assert all(jax.tree.leaves(finite))
    # loss should be near log(V) at init (uniform predictions)
    assert float(metrics["ce"]) < jnp.log(cfg.vocab_size) * 2


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_consistency(arch):
    """Logits for token S from full prefill == prefill(S-1) + decode(1)."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init(key)
    B, S, max_len = 2, 8, 32
    batch = _batch(key, cfg, B, S)

    cache_a = model.init_cache(B, max_len)
    logits_a, _, _ = model.forward_serve(params, batch, cache_a, 0)

    batch_prefix = dict(batch)
    batch_prefix["tokens"] = batch["tokens"][:, : S - 1]
    cache_b = model.init_cache(B, max_len)
    _, cache_b, enc = model.forward_serve(params, batch_prefix, cache_b, 0)
    batch_last = {"tokens": batch["tokens"][:, S - 1:]}
    logits_b, _, _ = model.forward_serve(params, batch_last, cache_b, S - 1,
                                         enc_out=enc)
    a = np.asarray(logits_a.astype(jnp.float32))
    b = np.asarray(logits_b.astype(jnp.float32))
    rel = np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-9)
    assert rel < 0.05, f"prefill/decode mismatch rel={rel}"


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "xlstm-1.3b"])
def test_tiny_training_reduces_loss(arch):
    """A few SGD steps on a repeated batch reduce the loss."""
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    batch = _batch(key, cfg, 4, 16)

    @jax.jit
    def step(params, lr=0.5):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                              params, grads)
        return params, loss

    losses = []
    for _ in range(8):
        params, loss = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses


def test_full_config_param_counts():
    """Analytic param counts of the FULL configs are in the right ballpark
    (config dims are exercised for real only via the dry-run).

    xlstm lands at ~2.6B: the assigned config gives d_ff=0 and leaves block
    sizing to xLSTM paper defaults (mLSTM projection factor 2, full-width
    q/k/v), which is larger than the branded 1.3B (see DESIGN.md §5).
    """
    expected = {
        "mistral-large-123b": (110e9, 135e9),
        "gemma-7b": (7.5e9, 10e9),      # 8.5B incl. 0.79B embeddings
        "internlm2-1.8b": (1.5e9, 2.2e9),
        "qwen2-72b": (65e9, 80e9),
        "deepseek-moe-16b": (14e9, 20e9),
        "dbrx-132b": (120e9, 145e9),
        "phi-3-vision-4.2b": (3.5e9, 4.6e9),
        "recurrentgemma-9b": (7.5e9, 11e9),
        "xlstm-1.3b": (2.0e9, 3.2e9),
        "whisper-tiny": (20e6, 80e6),
    }
    for arch, (lo, hi) in expected.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]"


def test_param_count_analytic_close_to_exact():
    """The analytic count used for MODEL_FLOPS must track the real init."""
    from repro.models.model_zoo import param_count_exact
    for arch in ("internlm2-1.8b", "xlstm-1.3b", "deepseek-moe-16b",
                 "recurrentgemma-9b", "whisper-tiny"):
        cfg = get_config(arch, smoke=True)
        exact = param_count_exact(cfg)
        approx = cfg.param_count()
        assert 0.5 < approx / exact < 2.0, (arch, approx, exact)


def test_moe_active_params_below_total():
    cfg = get_config("deepseek-moe-16b")
    assert cfg.active_param_count() < 0.3 * cfg.param_count()


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_config_is_same_family(arch):
    full, smoke = get_config(arch), get_config(arch, smoke=True)
    assert full.family == smoke.family
    assert full.is_encoder_decoder == smoke.is_encoder_decoder
    assert bool(full.moe.num_experts) == bool(smoke.moe.num_experts)
    assert (full.window > 0) == (smoke.window > 0)
