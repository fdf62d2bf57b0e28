"""The MoE metrics' readers (`moe_ffn_ms_per_step`, `moe_route_ms_per_step`,
`moe_expert_roofline`, and `bench/lib/moeload.py` under the last) on a
synthetic trace with numbers worked by hand; and the `moe.load` spans of
traced tiny runs on the CPU: a MoE stack marks each step, a dense stack
marks none and its readers read nothing."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tinymoe  # noqa: E402
from bench.lib import moeload, progspans, spec, tracecut  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
BODY = "jit(step)/while/body/closed_call/moe_ffn/"
ROUTE = BODY + "moe_route/dot_general"
EXPERTS = BODY + "moe_experts/pim_linear/dot_general"
SHARED = BODY + "pim_linear/dot_general"
# (plane, line, name, start us, duration us[, scope]).  Harness spans:
# step 10-110, deliver 110-120, step 130-230.  Step 1: routing 40-50, the
# held experts 50-100, the shared experts 100-105, the combine 105-108;
# step 2: routing 150-155, experts 155-205, then the decode kernel.
# `moe.load` spans: (600 assignments, 40 active) at 108 in step 1, (1200,
# 64) at 210 in step 2, and one at 115, outside every step.
SYNTHETIC = [
    (HOST, "python3", "step", 10, 100),
    (HOST, "python3", "deliver", 110, 10),
    (HOST, "python3", "step", 130, 100),
    (DEV, "XLA Ops", "fusion.3", 40, 10, ROUTE),
    (DEV, "XLA Ops", "fusion.4", 50, 50, EXPERTS),
    (DEV, "XLA Ops", "fusion.5", 100, 5, SHARED),
    (DEV, "XLA Ops", "fusion.6", 105, 3, ROUTE),
    (DEV, "XLA Ops", "fusion.3", 150, 5, ROUTE),
    (DEV, "XLA Ops", "fusion.4", 155, 50, EXPERTS),
    (DEV, "XLA Ops", "_pim_decode_pallas.8", 205, 5),
]
LOADS = [(108, 600, 40), (115, 9, 9), (210, 1200, 64)]
# hidden 64, expert width 32: 3 * 64 * 32 = 6,144 weights an expert
CONFIG = {"hidden_size": 64, "moe_intermediate_size": 32}
PEAKS = {"int8_ops": 1e12, "hbm_bytes_per_s": 1e10}
METRICS = ("moe_ffn_ms_per_step", "moe_route_ms_per_step",
           "moe_expert_roofline")


def _read(monkeypatch, rows, loads, name, shift=0.0):
    evs = [tracecut.Event(p, li, n, s * 1e3, d * 1e3, *sc)
           for p, li, n, s, d, *sc in rows]
    host = sorted((e for e in evs if not e.plane.startswith("/device:")),
                  key=lambda e: e.start)
    monkeypatch.setattr(progspans, "host_spans", lambda root: [
        tracecut.Event(e.plane, e.line, e.name, e.start + shift, e.dur)
        for e in host])
    monkeypatch.setattr(moeload, "load_spans", lambda root: tuple(
        (s * 1e3 + shift, a, t) for s, a, t in loads))
    ctx = SimpleNamespace(reduction=tracecut.reduce(evs), steps=[],
                          config=CONFIG, peaks=PEAKS)
    return spec.metric_reader(ROOT, name)(ctx)


def test_ffn_and_route_per_step(monkeypatch):
    # moe_ffn/: 10 + 50 + 5 + 3 in step 1, 5 + 50 in step 2; moe_route/:
    # 10 + 3 and 5
    assert _read(monkeypatch, SYNTHETIC, LOADS, "moe_ffn_ms_per_step") == (
        pytest.approx((68 + 55) / 2 * 1e-3))
    assert _read(monkeypatch, SYNTHETIC, LOADS,
                 "moe_route_ms_per_step") == pytest.approx(18 / 2 * 1e-3)


def test_expert_roofline(monkeypatch):
    # step 1: ops 2 * 6144 * 600 / 1e12 = 7.37 us, bytes 6144 * 40 / 1e10
    # = 24.58 us; step 2: 14.75 and 39.32 us: both bytes-bound; the span
    # at 115 lies in no step.  The experts ran 50 + 50 us.
    least = 6144 * (40 + 64) / 1e10
    assert _read(monkeypatch, SYNTHETIC, LOADS, "moe_expert_roofline") == (
        pytest.approx(100 * least / 100e-6))
    assert 100 * least / 100e-6 == pytest.approx(63.8976)


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("trace", ["dense", "no_load_span", "another_run"])
def test_none_without_scopes_or_spans(monkeypatch, name, trace):
    """A dense stack's trace (no MoE scope, no `moe.load` span) reads None
    in every metric; without the spans, or where the newest trace is
    another run's, the roofline reads None and the scope metrics read on."""
    rows, loads, shift = SYNTHETIC, LOADS, 0.0
    if trace == "dense":
        rows, loads = [r[:5] for r in rows], []
    elif trace == "no_load_span":
        loads = []
    else:
        shift = 1e6
    got = _read(monkeypatch, rows, loads, name, shift)
    none = trace == "dense" or name == "moe_expert_roofline"
    assert (got is None) == none, got


@pytest.mark.parametrize("cell", [tinymoe.CELL, "tiny.chat"])
def test_traced_run_marks_the_load_of_moe_steps_only(tmp_path, monkeypatch,
                                                     cell):
    """A traced tiny run on the CPU: the MoE cell's trace holds a
    `moe.load` span in each traced step, with positive counts and at most
    one active (forward, layer, held expert) triple per assignment; the
    dense cell's holds none, and no device op of either lies in a MoE
    scope on the CPU, so the three readers read nothing there."""
    from bench.lib import counts, harness
    root = tinymoe.make(tmp_path / "root")
    seen = {}
    real = harness.per_layer

    def spy(cell_, layout, red, steps, device_kind):
        seen.update(red=red)
        out = real(cell_, layout, red, steps, device_kind)
        seen.update(out=out)
        return out
    monkeypatch.setattr(harness, "per_layer", spy)
    monkeypatch.setitem(counts.PEAKS, "cpu", counts.PEAKS["TPU v5e"])
    harness.run_cell(root, cell, 2**33 + 7, 1.0, True, require_chip=False,
                     cache=False)
    red = seen["red"]
    metric = root / "bench" / "metrics" / "moe_expert_roofline.py"
    loads = moeload.step_loads(SimpleNamespace(reduction=red), metric)
    assert not any(m in seen["out"] for m in METRICS)
    assert not [e for e in red.ops if "moe_" in e.scope]
    if cell == "tiny.chat":
        assert loads is None and not moeload.load_spans(root)
        return
    assert loads and len(loads) == len(red.steps())
    assert all(0 < active <= assignments for assignments, active in loads)
