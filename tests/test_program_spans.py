"""The program's own trace marks: host spans inside `Scheduler.step` and the
name scopes on the linears and the KV pool relayout.

  * a traced run of a small paged `Scheduler` with mixed steps: every step
    that dispatched holds `sched.admit`, `sched.plan`, `sched.dispatch`,
    `sched.readback` and `sched.commit` in that order, on the mixed and on
    the decode chunk-scan path, and the dispatch span names its program
  * no program span carries a name the benchmark harness keys on
  * the served tokens are the same with the profiler on and off
  * the lowered HLO of a forward over the paged pool names ops under
    `pim_linear/` and `kv_relayout/`, none of them inside the decode
    kernel's jitted wrapper
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config
from repro.data import pipeline as data
from repro.models.model_zoo import build_model
from repro.runtime import serve_lib

HARNESS = ("submit", "step", "deliver")
PHASES = ["sched.admit", "sched.plan", "sched.dispatch", "sched.readback",
          "sched.commit", "sched.commit"]
SCHED = dict(max_batch_slots=2, max_len=64, page_size=8, decode_chunk=4,
             mixed_steps=True, prefill_chunk_budget=8)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("internlm2-1.8b", smoke=True)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _serve(model, params, prompts):
    """Serve `prompts` one `step` span per scheduler step; the tokens."""
    sched = serve_lib.Scheduler(model, params, **SCHED)
    rids = [sched.submit(p, 6) for p in prompts]
    while sched.queue or any(r is not None for r in sched.slot_req):
        with TraceAnnotation("step"):
            sched.step()
    return {r: sched.requests[r].tokens for r in rids}


def _host_events(directory):
    path = next(directory.rglob("*.xplane.pb"))
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats))
                   for plane in ProfileData.from_file(str(path)).planes
                   if not plane.name.startswith("/device:")
                   for line in plane.lines for e in line.events),
                  key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced(smoke_model, tmp_path_factory):
    cfg, model, params = smoke_model
    full = np.asarray(data.lm_batch(3, 2, 20, cfg.vocab_size))
    prompts = [full[0, :5].tolist(), full[1].tolist()]
    _serve(model, params, prompts)               # compiles outside the trace
    directory = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(directory))
    try:
        tokens = _serve(model, params, prompts)
    finally:
        jax.profiler.stop_trace()
    return prompts, tokens, _host_events(directory)


def test_every_step_holds_the_phases_in_order(traced):
    _, _, events = traced
    steps = [e for e in events if e[0] == "step"]
    programs = set()
    for _, lo, hi, _ in steps:
        inner = [e for e in events
                 if e[0].startswith("sched.") and lo <= e[1] and e[2] <= hi]
        assert [e[0] for e in inner] == PHASES
        programs.add(inner[2][3]["program"])
    # prefill chunks ride mixed steps; once both prompts are in, the rest
    # decodes in chunk-scans
    assert programs == {"mixed", "decode_scan"}


def test_no_program_span_takes_a_harness_name(traced):
    _, _, events = traced
    names = {e[0] for e in events if e[0].startswith("sched.")}
    assert names == set(PHASES)
    # the only harness-named spans are the `step`s this test opened, one
    # per scheduler step
    harness = [e[0] for e in events if e[0] in HARNESS]
    assert harness == ["step"] * len(
        [e for e in events if e[0] == "sched.admit"])


def test_tokens_same_with_the_profiler_on_and_off(smoke_model, traced):
    _, model, params = smoke_model
    prompts, tokens, _ = traced
    assert _serve(model, params, prompts) == tokens
    assert all(len(t) == 6 for t in tokens.values())


def test_scopes_reach_the_lowered_hlo():
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              attn_impl="kernel")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = model.init_cache(1, 32, ragged=True, page_size=8, num_pages=5)
    pages = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    def forward(params, tokens, cache):
        return model.forward_serve(params, {"tokens": tokens}, cache,
                                   jnp.asarray([3], jnp.int32),
                                   seq_lens=jnp.asarray([1], jnp.int32),
                                   pages=pages)

    hlo = jax.jit(forward).lower(
        params, jax.ShapeDtypeStruct((1, 1), jnp.int32),
        cache).as_text(dialect="hlo", debug_info=True)
    names = [line.split('op_name="', 1)[1].split('"', 1)[0]
             for line in hlo.splitlines() if 'op_name="' in line]
    linear = [n for n in names if "pim_linear/" in n]
    relayout = [n for n in names if "kv_relayout/" in n]
    assert any(n.endswith("/dot_general") for n in linear)
    assert any(n.endswith("/transpose") for n in relayout)
    assert not [n for n in linear + relayout
                if "jit(pim_decode_pallas)" in n
                or "jit(pim_attention_pallas)" in n]
    # the decode kernel's wrapper is in the program, beside the scopes
    assert any("jit(pim_decode_pallas)" in n for n in names)
