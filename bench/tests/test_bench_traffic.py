"""The traffic generator and the latency arithmetic, on the CPU."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from bench.lib import latency  # noqa: E402
from bench.lib.traffic import Traffic, poisson_gaps, quantile_grid  # noqa: E402

MIX = {
    "arrivals": {"kind": "poisson", "rate_per_s": 2.0, "warm_s": 1.0},
    "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "min": 32, "max": 2048},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                   "min": 16, "max": 512},
}


def _draw(seed, n=600):
    t = Traffic(MIX, seed, vocab=92544, max_len=2560)
    reqs = [t.next_request() for _ in range(n)]
    gaps = [t.next_gap() for _ in range(n)]
    return reqs, gaps


def test_same_seed_same_traffic():
    a, ga = _draw(2**33 + 5)
    b, gb = _draw(2**33 + 5)
    assert a == b and ga == gb


def test_seeds_share_the_work_and_differ_in_ids():
    (a, ga), (b, gb) = _draw(1, 700), _draw(2, 700)
    assert [(len(p), o) for p, o in a] == [(len(p), o) for p, o in b]
    assert ga == gb
    assert a[0][0] != b[0][0]
    # the first 512 requests hold every quantile once, in a shuffled order
    lens = [len(p) for p, _ in a[:512]]
    assert sorted(lens) == sorted(quantile_grid(MIX["prompt_len"]).tolist())
    assert lens != sorted(lens)


@pytest.mark.parametrize("dist,median,lo,hi", [
    ({"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 32,
      "max": 2048}, 512, 32, 2048),
    ({"dist": "uniform", "min": 1536, "max": 2560}, 2048, 1536, 2560),
    ({"dist": "fixed", "value": 2048}, 2048, 2048, 2048),
])
def test_quantile_grid(dist, median, lo, hi):
    g = quantile_grid(dist)
    assert g.min() >= lo and g.max() <= hi
    assert abs(np.median(g) - median) <= 0.01 * median + 1


def test_poisson_gaps_mean_rate():
    assert np.mean(poisson_gaps(2.0, 4096)) == pytest.approx(0.5, rel=0.01)


def test_halton_order_spreads_every_short_run():
    mix = dict(MIX, order="halton")
    a = Traffic(mix, 1, vocab=92544, max_len=2560)
    b = Traffic(mix, 2**33 + 7, vocab=92544, max_len=2560)
    ra = [a.next_request() for _ in range(64)]
    rb = [b.next_request() for _ in range(64)]
    assert [(len(p), o) for p, o in ra] == [(len(p), o) for p, o in rb]
    assert [a.next_gap() for _ in range(8)] == [b.next_gap() for _ in range(8)]
    prompts = [len(p) for p, _ in ra]
    outputs = [o for _, o in ra]
    # any 8 consecutive requests hold 3 to 5 prompts and outputs below
    # their medians (base-2 points: exactly 4 in each aligned 8)
    for i in range(len(ra) - 8):
        assert 3 <= sum(x < 512 for x in prompts[i:i + 8]) <= 5
        assert 2 <= sum(x < 128 for x in outputs[i:i + 8]) <= 6
    # prompt and output lengths are not tied to each other
    assert abs(np.corrcoef(prompts, outputs)[0, 1]) < 0.3
    with pytest.raises(ValueError):
        Traffic(dict(MIX, order="sorted"), 1, vocab=10, max_len=64)


def test_outputs_fit_the_cache():
    t = Traffic(dict(MIX, output_len={"dist": "fixed", "value": 4000}), 3,
                vocab=100, max_len=2560)
    for _ in range(50):
        p, o = t.next_request()
        assert len(p) + o <= 2559 and o >= 1
        assert all(0 <= x < 100 for x in p)


def _window(stall_at=None, stall=0.0):
    """Ten requests arriving 1 s apart, each served 50 tokens at 20 ms per
    token from 100 ms after its arrival; a stall delays everything after
    `stall_at` by `stall` seconds."""
    recs = []
    for i in range(10):
        r = latency.Record(i, float(i), [0] * 8, 50)
        for k in range(50):
            t = i + 0.1 + 0.02 * k
            if stall_at is not None and t >= stall_at:
                t += stall
            r.tokens.append((t, 1))
            r.ids.append(0)
        recs.append(r)
    return latency.summarize(recs, 0.0, 10.0, 12.0)


def test_a_stall_raises_both_tails_and_lowers_the_rate():
    calm = _window()
    stalled = _window(stall_at=4.5, stall=2.0)
    assert calm["ttft_p90_ms"] == pytest.approx(100.0)
    assert calm["tpot_p90_ms"] == pytest.approx(20.0)
    assert stalled["ttft_p90_ms"] > calm["ttft_p90_ms"]
    assert stalled["tpot_p90_ms"] > calm["tpot_p90_ms"]
    # the judged medians: half the arrivals come after the stall
    assert calm["ttft_p50_ms"] == pytest.approx(100.0)
    assert stalled["ttft_p50_ms"] > calm["ttft_p50_ms"]
    assert stalled["output_tokens_per_s"] < calm["output_tokens_per_s"]


def test_tpot_of_chunked_deliveries():
    # 8 tokens every 0.8 s: 0.1 s per token, whether or not the window
    # starts on a delivery
    r = latency.Record(0, 0.0, [0], 100)
    r.tokens = [(0.8 * i, 8) for i in range(1, 10)]
    assert latency.tpot_samples([r], 0.0, 10.0) == [pytest.approx(0.1)]
    assert latency.tpot_samples([r], 2.0, 10.0) == [pytest.approx(0.1)]
    assert latency.tpot_samples([r], 6.5, 7.0) == []


def test_request_without_token_counts_at_drain_end():
    r = latency.Record(0, 1.0, [0], 4)
    assert latency.ttft_samples([r], 0.0, 5.0, 9.0) == [8.0]
    assert latency.ttft_samples([r], 2.0, 5.0, 9.0) == []
