"""Operations and bytes the algorithm needs, and the chip's peaks.

The kernels' counts are the work attention needs, whatever implements it:
QK and AV multiply-adds over the valid keys only, and the bytes of q, of
the K/V pages actually read (with their scales) once per KV head, and of
the output.  The LUT reads, padding and re-reads are cost, not work.  An
operation is a multiply or an add, so a multiply-add counts 2.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

# Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  JAX names the chip
# "TPU v5 lite".
PEAKS: Dict[str, Dict[str, float]] = {
    kind: {"bf16_flops": 197e12, "int8_ops": 393e12,
           "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    for kind in ("TPU v5 lite", "TPU v5e")
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of `device_kind`; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def kv_bytes_per_token_head(head_dim: int, kv_bits: int) -> int:
    """K and V values of one token and KV head, plus their two f32 scales."""
    return 2 * head_dim * kv_bits // 8 + 2 * 4


def attention_call(rows: Iterable[Tuple[int, int]], *, heads: int,
                   kv_heads: int, head_dim: int, page_size: int,
                   kv_bits: int = 8) -> Tuple[float, float]:
    """(ops, bytes) of one attention kernel call over `rows`, each
    (query tokens n, KV length after them L): the queries sit at positions
    L - n .. L - 1 and attend causally, so query i sees L - n + i + 1 keys."""
    ops = 0.0
    nbytes = 0.0
    for n, L in rows:
        if n <= 0:
            continue
        keys = n * (L - n) + n * (n + 1) / 2        # summed over queries
        ops += 4.0 * heads * head_dim * keys         # QK and AV, 2 per MAC
        pages = -(-L // page_size)
        nbytes += (pages * page_size * kv_heads
                   * kv_bytes_per_token_head(head_dim, kv_bits))
        nbytes += n * heads * (head_dim + 4)         # int8 q and its scale
        nbytes += n * heads * head_dim * 4           # f32 output
    return ops, nbytes


def least_seconds(ops: float, nbytes: float, device_kind: str,
                  peak: str = "int8_ops") -> float:
    """The roofline's least time: the slower of compute and HBM."""
    p = peaks(device_kind)
    return max(ops / p[peak], nbytes / p["hbm_bytes_per_s"])


def model_ops(cfg: Dict, layout, tokens: int, logit_rows: int,
              attn_keys: float) -> float:
    """Model operations of `tokens` processed (the linears' ops of the
    cell's layout, `layout.linear_work`), `logit_rows` vocabulary-head rows,
    and attention over `attn_keys` (query, key) pairs summed over tokens:
    4 * H * Dh per pair per attention layer."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    linear_ops, _ = layout.linear_work(cfg, tokens)
    return (linear_ops + 2.0 * d * v * logit_rows
            + 4.0 * h * dh * layout.attention_layers(cfg) * attn_keys)
