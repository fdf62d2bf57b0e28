"""The MoE layer as one chip's share of an expert-parallel deployment, on
the CPU at a small size: the shares add up to the uncut layer, routing is
dropless (a request's logits do not depend on its step-mates), the gates
and the dense prefix follow the config, a pattern with a tail keeps its
kinds, and the load counts reach the scheduler's `moe.load` spans."""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import moe as M
from repro.models.model_zoo import build_model, param_count_exact
from repro.runtime import serve_lib

D, F, E, K, SHARED = 64, 32, 16, 6, 2

BASE = ModelConfig(
    name="moe-test", family="moe", num_layers=3, d_model=D, num_heads=4,
    num_kv_heads=4, head_dim=16, d_ff=F, dense_d_ff=96, vocab_size=128,
    max_seq_len=128, block_pattern=("moe",), num_dense_layers=1,
    moe=MoEConfig(num_experts=E, num_shared=SHARED, top_k=K,
                  norm_topk_prob=False))
FLOAT = dict(pim_linears=False, compute_dtype="float32")


def _share(cfg, n_held, first):
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_held=n_held, first_held=first))


def _layer_params(cfg, seed=0):
    return M.moe_ffn_init(jax.random.PRNGKey(seed), cfg)


def _slice_experts(params, first, n):
    return dict(params, experts={k: v[first:first + n]
                                 for k, v in params["experts"].items()})


def _tokens(T, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (T, D), jnp.float32)


@pytest.mark.parametrize("pim", [False, True])
def test_eight_shares_add_up_to_the_uncut_layer(pim):
    """Each of 8 chips holds 2 of the 16 experts: their routed parts, with
    the shared experts (which every chip computes alike) counted once,
    give the whole layer's output; so do the counts of assignments."""
    cfg = dataclasses.replace(BASE, compute_dtype="float32",
                              pim_linears=pim)
    params = _layer_params(cfg)
    x = _tokens(24)
    whole, _, whole_load = M.moe_ffn_held(params, x, cfg)
    shared = M._ffn_stack(jnp.broadcast_to(x, (SHARED,) + x.shape),
                          params["shared"], cfg).sum(0)
    parts, assignments, active = [], 0, 0
    for s in range(8):
        y, _, load = M.moe_ffn_held(_slice_experts(params, 2 * s, 2), x,
                                    _share(cfg, 2, 2 * s))
        parts.append(y - shared)
        assignments += int(load[0])
        active += int(load[1])
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-5,
                               atol=1e-5)
    assert assignments == int(whole_load[0]) == 24 * K
    assert active == int(whole_load[1]) <= E


def test_gates_renormalised_only_when_the_config_says():
    params = _layer_params(BASE)
    x = _tokens(10)
    probs = np.asarray(jax.nn.softmax(x @ params["router"], axis=-1))
    ex, sh = params["experts"], params["shared"]

    def swiglu(w, e):
        return (jax.nn.silu(x @ w["w_gate"][e]) * (x @ w["w_in"][e])
                ) @ w["w_out"][e]

    outs = {}
    for norm in (False, True):
        cfg = dataclasses.replace(
            BASE, moe=dataclasses.replace(BASE.moe, norm_topk_prob=norm),
            **FLOAT)
        with jax.default_matmul_precision("highest"):
            got, _, _ = M.moe_ffn_held(params, x, cfg)
            want = sum(swiglu(sh, e) for e in range(SHARED))
            top = np.argsort(-probs, axis=-1)[:, :K]
            for t in range(10):
                g = probs[t, top[t]]
                if norm:
                    g = g / g.sum()
                for e, w in zip(top[t], g):
                    want = want.at[t].add(w * swiglu(ex, e)[t])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        outs[norm] = got
    assert not np.allclose(outs[False], outs[True])


def test_dense_prefix_takes_the_dense_width():
    params = jax.eval_shape(build_model(BASE).init, jax.random.PRNGKey(0))
    mlp = params["dense_prefix"][0]["mlp"]
    assert mlp["w_gate"]["w"].shape == (D, 96)
    assert mlp["w_out"]["w"].shape == (96, D)
    assert params["blocks"][0]["moe"]["experts"]["w_in"].shape == (2, E, D, F)
    # without the key the dense width is d_ff, as before
    plain = jax.eval_shape(
        build_model(dataclasses.replace(BASE, dense_d_ff=0)).init,
        jax.random.PRNGKey(0))
    assert plain["dense_prefix"][0]["mlp"]["w_in"]["w"].shape == (D, F)
    # a share holds its experts only; the router keeps all of them
    held = jax.eval_shape(build_model(_share(BASE, 4, 8)).init,
                          jax.random.PRNGKey(0))["blocks"][0]["moe"]
    assert held["experts"]["w_gate"].shape == (2, 4, D, F)
    assert held["router"].shape == (2, D, E)
    full = get_config("deepseek-moe-16b")
    assert (full.resolved_dense_d_ff, full.d_ff) == (10944, 1408)
    assert not full.moe.norm_topk_prob and full.max_seq_len == 4096


@pytest.mark.parametrize("pattern,layers,dense,tail", [
    (("moe",), 3, 1, ()),
    (("moe", "moe", "attn"), 8, 4, ("moe",)),
    (("moe", "attn"), 6, 1, ("moe",)),
])
def test_layer_kinds_with_a_dense_prefix_and_a_tail(pattern, layers, dense,
                                                    tail):
    """The tail follows the dense prefix: its layers keep their pattern's
    kind (a tail layer within the first `num_dense_layers` of the stack's
    count, but past the prefix, is a MoE layer), and the analytic count is
    the tree's, less the final norm."""
    cfg = dataclasses.replace(BASE, block_pattern=pattern, num_layers=layers,
                              num_dense_layers=dense)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(params["dense_prefix"]) == dense
    assert tuple("moe" if "moe" in p else "attn"
                 for p in params["tail"]) == tail
    assert param_count_exact(cfg) == cfg.param_count() + D
    x = {"tokens": jnp.arange(6, dtype=jnp.int32)[None] % 128}
    logits, aux = jax.jit(model.forward_train)(
        model.init(jax.random.PRNGKey(0)), x)
    assert logits.shape == (1, 6, 128) and bool(jnp.isfinite(aux))


def _paged(cfg, B=4, max_len=32, ps=16, pages=9):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    cache = model.init_cache(B, max_len, ragged=True, page_size=ps,
                             num_pages=pages)
    table = np.arange(1, 1 + B * (max_len // ps), dtype=np.int32).reshape(
        B, max_len // ps)
    return model, params, cache, jnp.asarray(table)


def _serve(model, params, cache, table, toks, offs, lens, dec=None):
    fn = jax.jit(lambda p, c, t, o, s, d: model.forward_serve(
        p, {"tokens": t}, c, o, seq_lens=s, pages=table, decode_rows=d,
        moe_load=True))
    logits, cache, _, load = fn(params, cache, jnp.asarray(toks),
                                jnp.asarray(offs, jnp.int32),
                                jnp.asarray(lens, jnp.int32),
                                None if dec is None else jnp.asarray(dec))
    return np.asarray(logits), cache, np.asarray(load)


def test_dropless_logits_do_not_depend_on_step_mates():
    """A request's prefill and decode logits are bit-identical served alone
    and beside three others (the kernel path, the paged pool, a held
    share); capacity routing of the same tokens is not."""
    cfg = dataclasses.replace(_share(BASE, 8, 4), attn_impl="kernel")
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, (4, 8)).astype(np.int32)
    runs = []
    for mates in (False, True):
        model, params, cache, table = _paged(cfg)
        lens = [8, 8 if mates else 0, 5 if mates else 0, 8 if mates else 0]
        l0, cache, _ = _serve(model, params, cache, table, prompt,
                              [0] * 4, lens)
        nxt = np.zeros((4, 8), np.int32)
        nxt[:, 0] = l0.argmax(-1)
        l1, _, _ = _serve(model, params, cache, table, nxt, lens,
                          [1 if n else 0 for n in lens],
                          [n > 0 for n in lens])
        runs.append((l0[0], l1[0]))
    np.testing.assert_array_equal(runs[0][0], runs[1][0])
    np.testing.assert_array_equal(runs[0][1], runs[1][1])

    full = dataclasses.replace(BASE, moe=dataclasses.replace(
        BASE.moe, capacity_factor=0.5), **FLOAT)
    params = _layer_params(full)
    x = _tokens(32)
    alone = x.at[:24].set(0.0)            # the request is the last 8 rows
    held = [M.moe_ffn_held(params, v, full)[0][24:] for v in (alone, x)]
    np.testing.assert_array_equal(held[0], held[1])
    capped = [M.moe_ffn_local(params, v, full)[0][24:] for v in (alone, x)]
    assert not np.array_equal(capped[0], capped[1])


def test_load_counts_valid_tokens_only():
    """Every valid token of a stack that holds every expert gives top_k
    assignments per MoE layer; padding and idle rows give none."""
    model, params, cache, table = _paged(BASE)
    toks = np.ones((4, 8), np.int32)
    lens = [8, 3, 0, 5]
    _, _, load = _serve(model, params, cache, table, toks, [0] * 4, lens)
    assert load[0] == sum(lens) * K * 2
    assert 1 <= load[1] <= 2 * E
    dense = build_model(get_config("internlm2-1.8b", smoke=True))
    p = dense.init(jax.random.PRNGKey(0))
    c = dense.init_cache(2, 16)
    out = dense.forward_serve(p, {"tokens": jnp.ones((2, 4), jnp.int32)}, c,
                              0, moe_load=True)
    np.testing.assert_array_equal(out[3], [0, 0])


@contextlib.contextmanager
def _recorded_spans(monkeypatch):
    seen = []

    @contextlib.contextmanager
    def span(name, **args):
        seen.append((name, args))
        yield

    monkeypatch.setattr(serve_lib, "_span", span)
    yield seen


@pytest.mark.parametrize("arch", ["moe", "dense"])
def test_scheduler_marks_the_load_of_each_step(monkeypatch, arch):
    """A MoE stack's scheduler marks every device step with `moe.load`
    after its readback and before its commit; its assignments add up to
    top_k per token per MoE layer over every token the steps computed.  A
    dense stack marks none."""
    if arch == "moe":
        cfg = dataclasses.replace(BASE, attn_impl="kernel")
    else:
        cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                                  attn_impl="kernel")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with _recorded_spans(monkeypatch) as seen:
        sched = serve_lib.Scheduler(
            model, params, max_batch_slots=2, max_len=48, page_size=16,
            num_pages=7, mixed_steps=True, prefill_chunk_budget=16)
        rids = [sched.submit(list(range(1, 1 + n)), 5) for n in (20, 9, 3)]
        sched.run()
    names = [n for n, _ in seen]
    loads = [a for n, a in seen if n == "moe.load"]
    if arch == "dense":
        assert not loads and "sched.readback" in names
        return
    assert len(loads) == names.count("sched.readback")
    for i, n in enumerate(names):
        if n == "moe.load":
            assert names[i - 1] == "sched.readback"
            assert names[i + 1] == "sched.commit"
    decoded = sum(len(sched.requests[r].tokens) - 1 for r in rids)
    tokens = sched.prefill_tokens_computed + decoded
    assert sum(a["assignments"] for a in loads) == tokens * K * 2
    assert all(0 < a["active"] for a in loads)
