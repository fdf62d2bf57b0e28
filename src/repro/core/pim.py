"""PIM macro behavioral model (AttentionLego §3.2) and the PIM linear layer.

The paper's APIM macro stores int8 weights in a 128x128 crossbar and computes
matrix-vector products in the analog domain: 16 word-lines are driven per step
(input parallelism 16) and each 16-row partial sum is digitized by a 6-bit ADC
(output parallelism 16), after which partial sums are accumulated digitally.

TPU adaptation: a 128x128 weight-stationary macro IS an MXU tile.  The
behavioral model below is pure jnp (the oracle); `repro.kernels.pim_matmul`
is the Pallas/MXU realization with identical semantics.

Two fidelity modes (cfg.adc_mode):
  * "ideal":      exact int32 accumulation (functional-correctness mode)
  * "quantized":  every 16-row partial sum passes through the saturating
                  6-bit ADC transfer before digital accumulation
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import PIMConfig
from repro.core import quant


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def adc_full_range(cfg: PIMConfig) -> float:
    """ADC full-scale: fraction of the theoretical max 16-row partial sum."""
    qmax_w = (1 << (cfg.weight_bits - 1)) - 1
    qmax_x = (1 << (cfg.input_bits - 1)) - 1
    return cfg.adc_range_frac * cfg.wordline_group * qmax_w * qmax_x


def pim_matmul_int(x_q: jax.Array, w_q: jax.Array, cfg: PIMConfig) -> jax.Array:
    """Integer-domain macro-tiled matmul: (..., K) int8 x (K, N) int8 -> (..., N).

    Returns float32 values that lie exactly on the accumulation grid
    (int32-exact in ideal mode; ADC-grid values in quantized mode).
    """
    K = x_q.shape[-1]
    assert w_q.shape[0] == K, (x_q.shape, w_q.shape)
    g = cfg.wordline_group
    x_p = _pad_to(x_q, -1, g)
    w_p = _pad_to(w_q, 0, g)
    Kp = x_p.shape[-1]
    if cfg.adc_mode == "ideal":
        y = jax.lax.dot_general(
            x_p.astype(jnp.int32), w_p.astype(jnp.int32),
            (((x_p.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return y.astype(jnp.float32)
    # quantized ADC: per 16-row-group partial sums through the ADC transfer
    G = Kp // g
    xg = x_p.reshape(x_p.shape[:-1] + (G, g)).astype(jnp.int32)
    wg = w_p.reshape(G, g, w_p.shape[-1]).astype(jnp.int32)
    # (..., G, N) partial sums — one per word-line group (one analog step)
    psum = jnp.einsum("...gk,gkn->...gn", xg, wg)
    psum = quant.adc_transfer(psum, cfg.adc_bits, adc_full_range(cfg))
    return jnp.sum(psum, axis=-2)


def pim_matmul(
    x: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    cfg: PIMConfig,
    x_scale: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Full PIM forward: dynamic per-token input quantization + int matmul + rescale."""
    if x_scale is None:
        x_scale = quant.symmetric_max_scale(x, cfg.input_bits, axis=-1)
    x_q = quant.quantize(x, x_scale, cfg.input_bits)
    y = pim_matmul_int(x_q, w_q, cfg)
    return (y * x_scale * w_scale).astype(out_dtype)


# ---------------------------------------------------------------------------
# PIM linear layer (QAT): forward through the behavioral model, fp backward
# ---------------------------------------------------------------------------
def quantize_weights(w: jax.Array, cfg: PIMConfig):
    """Per-output-channel symmetric weight quantization ("load once")."""
    axis = 0 if cfg.per_channel else None
    scale = quant.symmetric_max_scale(w, cfg.weight_bits, axis=axis)
    return quant.quantize(w, scale, cfg.weight_bits), scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pim_linear_core(x: jax.Array, w: jax.Array, cfg: PIMConfig) -> jax.Array:
    w_q, w_scale = quantize_weights(w, cfg)
    return pim_matmul(x, w_q, w_scale, cfg, out_dtype=x.dtype)


def _pim_linear_fwd(x, w, cfg):
    return _pim_linear_core(x, w, cfg), (x, w)


def _pim_linear_bwd(cfg, res, g):
    x, w = res
    # straight-through: gradient of the underlying fp matmul
    dx = jnp.einsum("...n,kn->...k", g, w.astype(g.dtype)).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    dw = jnp.einsum("bk,bn->kn", x2.astype(jnp.float32), g2.astype(jnp.float32))
    return dx, dw.astype(w.dtype)


_pim_linear_core.defvjp(_pim_linear_fwd, _pim_linear_bwd)


def pim_linear_init(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.float32):
    scale = 1.0 / (d_in ** 0.5)
    params = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
    return params


def pim_linear_apply(params, x: jax.Array, cfg: PIMConfig, enabled: bool = True):
    """Apply a linear layer, through the PIM behavioral model if `enabled`.

    Accepts either QAT params {"w": fp} or deployed params {"w_q", "w_scale"}.
    Its ops carry the name scope `pim_linear` (HLO `op_name`
    `.../pim_linear/...`), by which a profiler trace finds the linears'
    device time.
    """
    with jax.named_scope("pim_linear"):
        if "w_q" in params:
            y = pim_matmul(x, params["w_q"], params["w_scale"], cfg,
                           out_dtype=x.dtype)
        elif enabled:
            y = _pim_linear_core(x, params["w"].astype(x.dtype), cfg)
        else:
            y = x @ params["w"].astype(x.dtype)
        if "b" in params:
            y = y + params["b"].astype(y.dtype)  # digital-domain adder (qwen2 bias)
        return y


def deploy_params(params, cfg: PIMConfig):
    """Convert QAT params to deployed int8 macro contents (the one-time load)."""
    w_q, w_scale = quantize_weights(params["w"], cfg)
    out = {"w_q": w_q, "w_scale": w_scale}
    if "b" in params:
        out["b"] = params["b"]
    return out


# ---------------------------------------------------------------------------
# Cycle model (paper §3.2) — used by benchmarks/pim_cycles.py
# ---------------------------------------------------------------------------
def macro_grid(d_in: int, d_out: int, cfg: PIMConfig):
    rows = -(-d_in // cfg.macro_rows)
    cols = -(-d_out // cfg.macro_cols)
    return rows, cols


def mvm_cycles(d_in: int, d_out: int, cfg: PIMConfig) -> int:
    """Cycles for one input vector through a (d_in x d_out) PIM engine.

    Macros operate spatially in parallel; the row dimension is serialized over
    word-line groups and column groups per macro (64 cycles for 128x128), and
    row-tiles accumulate in the digital adder tree (pipelined, +1 cycle each).
    """
    rows, _ = macro_grid(d_in, d_out, cfg)
    return cfg.steps_per_mvm + (rows - 1)


def weight_load_cycles(d_in: int, d_out: int, cfg: PIMConfig) -> int:
    """One-time weight load: 128 row-writes per column per macro (paper §3.2)."""
    rows, cols = macro_grid(d_in, d_out, cfg)
    per_macro = cfg.macro_rows * cfg.macro_cols // 1  # serial row-writes per col
    return rows * cols * per_macro
