"""Operation and byte counts against hand-worked numbers at the cells'
shapes (internlm2-1.8b: H 16, Hkv 8, Dh 128, pages of 16, int8 KV)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.lib import counts, spec, work  # noqa: E402
from bench.lib.loop import StepRecord  # noqa: E402

CFG = json.loads((ROOT / "bench/configs/internlm2-1.8b.json").read_text())
LAYOUT = spec.layout_module(ROOT, CFG)
SHAPE = dict(heads=16, kv_heads=8, head_dim=128, page_size=16, kv_bits=8)


def test_v5e_peaks_and_unknown_kind():
    p = counts.peaks("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (
        197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        counts.peaks("TPU v4")


def test_kv_bytes_per_token():
    # 24 layers x 8 KV heads x (K + V int8 of 128 + two f32 scales)
    assert 24 * 8 * counts.kv_bytes_per_token_head(128, 8) == 50688
    assert counts.kv_bytes_per_token_head(128, 4) == 136


@pytest.mark.parametrize("rows,ops,nbytes", [
    # one decode row at KV length 2048: 2048 keys; 128 pages read
    ([(1, 2048)], 4 * 16 * 128 * 2048,
     128 * 16 * 8 * 264 + 16 * 132 + 16 * 128 * 4),
    # a 256-token chunk ending at 512: 256 * 256 + 256 * 257 / 2 keys
    ([(256, 512)], 4 * 16 * 128 * 98432,
     32 * 16 * 8 * 264 + 256 * 16 * 132 + 256 * 16 * 128 * 4),
    # idle rows add nothing
    ([(0, 999), (1, 16)], 4 * 16 * 128 * 16,
     16 * 8 * 264 + 16 * 132 + 16 * 128 * 4),
])
def test_attention_call(rows, ops, nbytes):
    assert counts.attention_call(rows, **SHAPE) == (ops, nbytes)


def test_decode_is_bandwidth_bound_long_chunk_compute_bound():
    ops, nb = counts.attention_call([(1, 2048)], **SHAPE)
    assert counts.least_seconds(ops, nb, "TPU v5 lite") == nb / 819e9
    # 256 queries at 1792..2047: 491,648 keys, 4.03 G ops (10.2 us) against
    # 6.97 MB (8.5 us)
    ops, nb = counts.attention_call([(256, 2048)], **SHAPE)
    assert ops == 4 * 16 * 128 * 491648
    assert counts.least_seconds(ops, nb, "TPU v5 lite") == ops / 393e12


def test_model_ops_internlm2():
    assert LAYOUT.matmul_params(CFG) == 24 * (2048 * 128 * 48
                                              + 3 * 2048 * 8192)
    # one decode token at 2048 keys: weights, the head row, attention
    want = 2 * 1_509_949_440 + 2 * 2048 * 92544 + 4 * 16 * 128 * 24 * 2048
    assert counts.model_ops(CFG, LAYOUT, 1, 1, 2048) == want == 3_801_612_288


def test_step_work():
    s = StepRecord(0.0, 1.0, prefill=[(256, 512)],
                   decode=[[2048, 1000], [2049]], delivered=3)
    ops, nb = counts.attention_call([(256, 512)], **SHAPE)
    assert work.kernel_least_seconds([s], CFG, LAYOUT, "TPU v5 lite",
                                     "prefill", 16) == pytest.approx(
                                         24 * nb / 819e9)
    dec = sum(counts.least_seconds(*counts.attention_call(
        [(1, L) for L in it], **SHAPE), "TPU v5 lite") for it in s.decode)
    assert work.kernel_least_seconds([s], CFG, LAYOUT, "TPU v5 lite",
                                     "decode", 16) == pytest.approx(24 * dec)
    keys = 98432 + 2048 + 1000 + 2049
    assert work.model_ops([s], CFG, LAYOUT) == counts.model_ops(
        CFG, LAYOUT, 259, 3, keys)
