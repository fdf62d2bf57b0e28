"""The readers of the program's spans and scopes (`bench/lib/progspans.py`
and its four metrics) on a synthetic trace with numbers worked by hand, and
the helper on the trace of a traced tiny run on the CPU."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tinyroot  # noqa: E402
from bench.lib import progspans, spec, tracecut  # noqa: E402
from bench.lib.loop import StepRecord  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
LIN = "jit(step)/while/body/closed_call/pim_linear/dot_general"
REL = "jit(step)/while/body/closed_call/kv_relayout/transpose"
# (plane, line, name, start us, duration us[, scope]).  Harness spans:
# submit 0-10, step 10-110, deliver 110-120, step 130-230.  Program spans
# in step 1: admit 12-20, plan 20-30, dispatch 30-60, readback 60-100,
# commit 100-106 and 106-108; in step 2: admit 132-140, plan 140-150,
# dispatch 150-170, readback 170-200, commit 200-220 and 222-228.  Device
# ops: a linear 50-95 (10 us under dispatch, 35 under readback) and the
# relayout 95-99; a linear 160-190 (10 and 20), the decode kernel 190-195
# and the relayout 195-197, both under readback.
SYNTHETIC = [
    (HOST, "python3", "submit", 0, 10),
    (HOST, "python3", "step", 10, 100),
    (HOST, "python3", "sched.admit", 12, 8),
    (HOST, "python3", "sched.plan", 20, 10),
    (HOST, "python3", "sched.dispatch", 30, 30),
    (HOST, "python3", "sched.readback", 60, 40),
    (HOST, "python3", "sched.commit", 100, 6),
    (HOST, "python3", "sched.commit", 106, 2),
    (HOST, "python3", "deliver", 110, 10),
    (HOST, "python3", "step", 130, 100),
    (HOST, "python3", "sched.admit", 132, 8),
    (HOST, "python3", "sched.plan", 140, 10),
    (HOST, "python3", "sched.dispatch", 150, 20),
    (HOST, "python3", "sched.readback", 170, 30),
    (HOST, "python3", "sched.commit", 200, 20),
    (HOST, "python3", "sched.commit", 222, 6),
    (DEV, "XLA Ops", "fusion.1", 50, 45, LIN),
    (DEV, "XLA Ops", "copy_bitcast_fusion.4", 95, 4, REL),
    (DEV, "XLA Ops", "fusion.1", 160, 30, LIN),
    (DEV, "XLA Ops", "_pim_decode_pallas.8", 190, 5),
    (DEV, "XLA Ops", "copy_bitcast_fusion.4", 195, 2, REL),
]
# hidden 64, d_ff 128, 4 q / 2 KV heads of 16, 2 layers: 73,728 weights
CONFIG = {"reference": "dense_decoder", "hidden_size": 64,
          "intermediate_size": 128, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2}
LAYOUT = spec.layout_module(ROOT, CONFIG)
PEAKS = {"int8_ops": 1e12, "hbm_bytes_per_s": 1e10}
# a mixed step (a 100-token chunk beside two decode rows: one forward of
# 102 tokens) and a decode chunk-scan of three recorded iterations
STEPS = [StepRecord(0, 1, [(100, 100)], [[30, 40]], 2),
         StepRecord(1, 2, [], [[41, 31], [42, 32], [43]], 5)]
METRICS = ("sched_prep_ms_per_step", "sched_commit_ms_per_step",
           "pim_linear_roofline", "kv_relayout_ms_per_step")


def _events(rows):
    return [tracecut.Event(p, li, n, s * 1e3, d * 1e3, *sc)
            for p, li, n, s, d, *sc in rows]


def _read(monkeypatch, rows, name):
    evs = _events(rows)
    red = tracecut.reduce(evs)
    monkeypatch.setattr(progspans, "host_spans", lambda root: sorted(
        (e for e in evs if not e.plane.startswith("/device:")),
        key=lambda e: e.start))
    ctx = SimpleNamespace(reduction=red, steps=STEPS, config=CONFIG,
                          layout=LAYOUT, peaks=PEAKS)
    return spec.metric_reader(ROOT, name)(ctx)


def test_prep_and_commit_per_step(monkeypatch):
    # step 1: admit 8 + plan 10 + dispatch 30 - 10 under the linear = 38;
    # step 2: 8 + 10 + 20 - 10 = 28
    assert _read(monkeypatch, SYNTHETIC, "sched_prep_ms_per_step") == (
        pytest.approx((38 + 28) / 2 * 1e-3))
    # step 1: readback 40 - 39 under the linear and the relayout, commits 8;
    # step 2: readback 30 - 27, commits 26
    assert _read(monkeypatch, SYNTHETIC, "sched_commit_ms_per_step") == (
        pytest.approx((9 + 29) / 2 * 1e-3))
    # the rest of the harness's host time per step lies between the spans:
    # 10-12 and 108-110 in step 1, 130-132, 220-222 and 228-230 in step 2
    red = tracecut.reduce(_events(SYNTHETIC))
    assert red.host_ms_per_step() == pytest.approx((38 + 9 + 4 + 28 + 29 + 6)
                                                   / 2 * 1e-3)


def test_relayout_per_step(monkeypatch):
    assert _read(monkeypatch, SYNTHETIC, "kv_relayout_ms_per_step") == (
        pytest.approx((4 + 2) / 2 * 1e-3))


def test_linear_roofline(monkeypatch):
    # 73,728 weights; the 102-token forward is compute-bound at
    # 2 * 73,728 * 102 / 1e12 s, the three decode forwards bytes-bound at
    # 73,728 / 1e10 s each; the linears ran 45 + 30 us
    least = 2 * 73728 * 102 / 1e12 + 3 * 73728 / 1e10
    assert _read(monkeypatch, SYNTHETIC, "pim_linear_roofline") == (
        pytest.approx(100 * least / 75e-6))
    assert least / 75e-6 == pytest.approx(0.49545216)


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("trace", ["neither", "no_program_span",
                                   "no_scoped_op", "another_run"])
def test_none_without_spans_or_scopes(monkeypatch, name, trace):
    """A program without the spans and scopes (`neither`) reads None in
    every metric; each metric reads None where what it reads is missing;
    the span metrics also where the newest trace is another run's."""
    rows = SYNTHETIC
    if trace in ("neither", "no_program_span"):
        rows = [r for r in rows if not r[2].startswith("sched.")]
    if trace in ("neither", "no_scoped_op"):
        rows = [r[:5] for r in rows]
    spans = name.startswith("sched_")
    if trace == "another_run":
        evs = _events(rows)
        host = [e for e in evs if not e.plane.startswith("/device:")]
        monkeypatch.setattr(progspans, "host_spans", lambda root: [
            tracecut.Event(e.plane, e.line, e.name, e.start + 1e6, e.dur)
            for e in host])
        ctx = SimpleNamespace(reduction=tracecut.reduce(evs), steps=STEPS,
                              config=CONFIG, layout=LAYOUT, peaks=PEAKS)
        got = spec.metric_reader(ROOT, name)(ctx)
        none = spans
    else:
        got = _read(monkeypatch, rows, name)
        none = (trace == "neither"
                or trace == ("no_program_span" if spans else "no_scoped_op"))
    assert (got is None) == none, got


def test_helper_finds_a_traced_runs_spans(tmp_path, monkeypatch):
    from bench.lib import counts, harness
    root = tinyroot.make(tmp_path / "root")
    seen = {}
    real = harness.per_layer

    def spy(cell, layout, red, steps, device_kind):
        seen.update(red=red, steps=steps)
        return real(cell, layout, red, steps, device_kind)
    monkeypatch.setattr(harness, "per_layer", spy)
    # the CPU has no peaks; the readers need some to run
    monkeypatch.setitem(counts.PEAKS, "cpu", counts.PEAKS["TPU v5e"])
    harness.run_cell(root, "tiny.chat", 2**33 + 5, 1.0, True,
                     require_chip=False, cache=False)
    red = seen["red"]
    metric = root / "bench" / "metrics" / "sched_prep_ms_per_step.py"
    spans = progspans.program_spans(SimpleNamespace(reduction=red), metric)
    steps = red.steps()
    assert spans and len(steps) == len(seen["steps"]) > 0
    names = {e.name for e in spans}
    assert {"sched.admit", "sched.dispatch", "sched.commit"} <= names
    assert names <= set(progspans.PREP + progspans.COMMIT)
    for s in steps:
        inner = [e for e in spans if s.start <= e.start and e.end <= s.end]
        assert inner and inner[0].name == "sched.admit"
    # a reduction of another window is not this trace's
    moved = tracecut.Reduction((red.window[0] + 1, red.window[1]),
                               red.n_devices, red.busy, red.ops, red.spans)
    assert progspans.program_spans(SimpleNamespace(reduction=moved),
                                   metric) is None
