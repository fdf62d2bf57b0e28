"""Trace reduction on a small synthetic trace (numbers worked by hand) and
on a recorded CPU profiler trace (the `.xplane.pb` loader)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1])]

from bench.lib import tracecut  # noqa: E402


DEV, HOST = "/device:TPU:0", "/host:CPU"
# (plane, line, name, start us, duration us[, scope]): device ops 100-150,
# 140-180 (_attn_kernel), 300-400 (_decode_kernel), 520-700 (run inside
# `_decode_kernel`'s jitted wrapper, by its scope), and 900-950 past the
# window, all inside a `while` op at 90-710 that is control flow, not work;
# a module line that is not an op line; harness spans submit 50-60, step
# 60-420, deliver 420-490, step 500-700
SYNTHETIC = [
    (DEV, "XLA Ops", "%while.3 = (s32[]) while(s32[] %x)", 90, 620),
    (DEV, "XLA Ops", "%fusion.1 = f32[8] fusion(f32[8] %p)", 100, 50),
    (DEV, "XLA Ops", "_attn_kernel", 140, 40),
    (DEV, "XLA Ops", "_decode_kernel", 300, 100),
    (DEV, "XLA Ops", "fusion.3", 520, 180,
     "jit(step)/jit(main)/while/body/jit(_decode_kernel)/gather"),
    (DEV, "XLA Ops", "fusion.9", 900, 50),
    (DEV, "XLA Modules", "jit_step", 100, 300),
    (HOST, "python3", "submit", 50, 10),
    (HOST, "python3", "step", 60, 360),
    (HOST, "python3", "deliver", 420, 70),
    (HOST, "python3", "step", 500, 200),
]


@pytest.fixture(scope="module")
def red():
    return tracecut.reduce([tracecut.Event(p, li, n, s * 1e3, d * 1e3, *sc)
                            for p, li, n, s, d, *sc in SYNTHETIC])


def test_window_and_busy_union(red):
    assert red.window == (50e3, 700e3)
    assert red.n_devices == 1
    assert red.busy[0] == [(100e3, 180e3), (300e3, 400e3), (520e3, 700e3)]
    assert red.busy_s == pytest.approx(360e-6)
    assert red.window_s == pytest.approx(650e-6)
    assert red.idle_share() == pytest.approx(1 - 360 / 650)


def test_kernel_time_by_name(red):
    assert red.kernel_seconds("_attn_kernel") == pytest.approx(40e-6)
    # the custom call by name, and its wrapper's fusion by scope
    assert red.kernel_seconds("_decode_kernel") == pytest.approx(280e-6)
    assert red.kernel_seconds("_no_such_kernel") == 0.0
    assert red.top_ops(2)[0] == ("fusion.3", pytest.approx(180e-6))
    assert ("%fusion.1", pytest.approx(50e-6)) in red.top_ops(5)
    assert not any("while" in name for name, _ in red.top_ops(10))


def test_host_time_per_step(red):
    # step 60-420 overlaps 180 us of device work, step 500-700 overlaps 180
    assert red.host_ms_per_step() == pytest.approx((180 + 20) / 2 * 1e-3)


def test_idle_gaps_named_by_span(red):
    gaps = red.idle_gaps()
    assert [g[0] for g in gaps] == ["step", "deliver", "step"]
    assert [g[1] for g in gaps] == pytest.approx([120e-6, 120e-6, 50e-6])


def test_no_span_is_an_error():
    with pytest.raises(ValueError):
        tracecut.reduce([tracecut.Event("/device:TPU:0", "XLA Ops", "f",
                                        0.0, 1.0)])


def test_recorded_xplane_spans():
    evs = tracecut.load_xplane(HERE / "data" / "cpu_spans.xplane.pb")
    red = tracecut.reduce(evs)
    assert [s.name for s in red.spans] == ["submit", "step", "deliver",
                                           "step"]
    assert red.window_s > 0
    # a CPU trace has no device plane: nothing is busy, nothing is read
    assert red.n_devices == 0 and red.host_ms_per_step() is None


def test_hlo_scopes_of_a_recorded_trace():
    # `outer` (jitted) calls `inner` (jitted), which gathers from a table
    path = HERE / "data" / "cpu_nested_jit.xplane.pb"
    scopes = tracecut.hlo_scopes(path)
    outer = scopes["jit_outer(7)"]
    assert outer["broadcast_multiply_fusion"] == "jit(outer)/jit(inner)/mul"
    assert "jit(inner)" not in outer["dot_general.1"]
    assert "jit(_take)/gather" in outer["gather.4"]
    # a module the trace names otherwise: every module's instruction
    assert tracecut._scope(scopes, "jit_outer(?)", "dot_general.1") == (
        "jit(outer)/dot_general")
    assert tracecut._scope(scopes, "", "no_such_op") == ""
    # the host plane's ops are no device ops: nothing gets a scope
    assert all(e.scope == "" for e in tracecut.load_xplane(path))
