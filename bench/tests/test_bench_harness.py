"""The whole run of a cell on the CPU at a tiny size, past the look for a
chip: a sound run comes out correct; the lower-precision control and a
timed path broken underneath come out not correct.

The faults a served cell can have: a step that leaves its state unchanged
(the KV pool is never written) and a token altered where it is produced
(the sampler).  A mean over half a batch and an exchange between chips do
not exist in a one-chip serving cell.
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import tinyroot  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("bench") / "root")


def _run(root, workload, seed, overrides=None):
    from bench.lib.harness import run_cell
    result, checks = run_cell(root, workload, seed, 2.0, False,
                              require_chip=False, cache=False,
                              overrides=overrides)
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) >= {"setup_s", "tpot_p50_ms",
                                      "output_tokens_per_s"}
    return result, dict((n, (v, lim)) for n, v, lim in checks)


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.sessions"])
def test_sound_run_is_correct(root, workload):
    result, checks = _run(root, workload, 2**33 + 11)
    gap, limit = checks["logit_gap"]
    assert result["correct"], checks
    assert 0 <= gap < limit and result["failed"] == 0
    assert result["attempted"] > 0


def _int4():
    from repro.configs.base import PIMConfig
    return {"pim": PIMConfig(weight_bits=4, input_bits=4), "kv_bits": 4}


def _token_altered(monkeypatch):
    from repro.runtime import serve_lib
    real = serve_lib.sample_logits_per_row

    def altered(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]
    monkeypatch.setattr(serve_lib, "sample_logits_per_row", altered)


def _state_unchanged(monkeypatch):
    from repro.core import attention
    monkeypatch.setattr(attention, "paged_cache_write",
                        lambda pool, *a, **k: pool)


@pytest.mark.parametrize("fault", ["int4_control", "token_altered",
                                   "state_unchanged"])
def test_broken_path_is_not_correct(root, monkeypatch, fault):
    overrides = None
    if fault == "int4_control":
        overrides = _int4()
    elif fault == "token_altered":
        _token_altered(monkeypatch)
    else:
        _state_unchanged(monkeypatch)
    result, checks = _run(root, "tiny.chat", 2**33 + 11, overrides)
    gap, limit = checks["logit_gap"]
    assert not result["correct"]
    assert gap is None or gap > limit
