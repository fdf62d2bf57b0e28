"""The work of recorded steps, for the readers of per-layer metrics.

Each step (`loop.StepRecord`) holds its prefill rows (tokens computed, KV
length after) and, per decode iteration, the KV length each decode row
attended.  Every attention layer calls the prefill kernel once for a step's
prefill rows and the decode kernel once per decode iteration; the cell's
layout says how many layers attend and what the linears' work is.
"""
from __future__ import annotations

from typing import Dict, Sequence

from bench.lib import counts


def _shape(cfg: Dict) -> Dict:
    return dict(heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                kv_bits=cfg["program"]["kv_bits"])


def kernel_least_seconds(steps: Sequence, cfg: Dict, layout,
                         device_kind: str, kernel: str,
                         page_size: int) -> float:
    """Summed roofline least time of every call of `kernel` ("prefill" or
    "decode") in `steps`, over the layout's attention layers."""
    shape = dict(_shape(cfg), page_size=page_size)
    total = 0.0
    for s in steps:
        if kernel == "prefill":
            calls = [s.prefill] if s.prefill else []
        else:
            calls = [[(1, L) for L in it] for it in s.decode]
        for rows in calls:
            ops, nbytes = counts.attention_call(rows, **shape)
            total += counts.least_seconds(ops, nbytes, device_kind)
    return total * layout.attention_layers(cfg)


def model_ops(steps: Sequence, cfg: Dict, layout) -> float:
    """Model operations of every token the steps processed."""
    tokens, keys, logits = 0, 0.0, 0
    for s in steps:
        for n, L in s.prefill:
            tokens += n
            keys += n * (L - n) + n * (n + 1) / 2
        for it in s.decode:
            tokens += len(it)
            keys += sum(it)
        logits += s.delivered
    return counts.model_ops(cfg, layout, tokens, logits, keys)


def mfu(ctx):
    """Model FLOP utilisation of a traced window, in percent: the model
    operations of every token its steps processed over the window's
    seconds and the chip's int8 peak (the paper's linears and score engine
    are int8).  None without a trace or steps."""
    if ctx.reduction is None or not ctx.steps:
        return None
    return (100.0 * model_ops(ctx.steps, ctx.config, ctx.layout)
            / ctx.reduction.window_s / ctx.peaks["int8_ops"])
