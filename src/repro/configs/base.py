"""Configuration dataclasses for the AttentionLego framework.

Everything is a frozen dataclass so configs hash/compare cleanly and can be
used as static arguments to jit.  The PIM section mirrors the paper's macro
micro-architecture (AttentionLego §3.2):

  * 128 x 128 macro array, 8-bit weights
  * input parallelism 16  -> 16 of 128 word-lines active per analog step
  * output parallelism 16 -> one 6-bit ADC shared by 8 columns
  * one full 128-wide MVM = 64 clock cycles
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# PIM macro behavioral model configuration (paper §3.2)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PIMConfig:
    """Behavioral model of the paper's APIM macro."""

    macro_rows: int = 128          # word-lines per macro
    macro_cols: int = 128          # bit-lines per macro
    weight_bits: int = 8           # in-array weight precision (paper: 8-bit)
    input_bits: int = 8            # DAC / input port precision (paper: 8-bit)
    adc_bits: int = 6              # ADC precision (paper: 6-bit)
    wordline_group: int = 16       # input parallelism: rows active per analog step
    # "ideal"      -> exact int32 accumulation (functional-correctness mode)
    # "quantized"  -> saturating `adc_bits` quantization of each 16-row partial sum
    adc_mode: str = "ideal"
    # ADC full-scale as a multiple of the per-group theoretical max |psum|.
    # Real designs calibrate this to activation statistics; 1/8 of full scale is
    # a reasonable default for zero-mean int8 activations (see benchmarks).
    adc_range_frac: float = 0.125
    # per-channel weight scales (standard digital calibration) vs per-tensor
    per_channel: bool = True

    @property
    def adc_levels(self) -> int:
        return 1 << self.adc_bits

    @property
    def steps_per_mvm(self) -> int:
        """Analog steps for one full macro MVM (paper: 128/16 * 128/16 = 64)."""
        return (self.macro_rows // self.wordline_group) * (self.macro_cols // 16)


# ---------------------------------------------------------------------------
# LUT softmax configuration (paper §3.4)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LUTSoftmaxConfig:
    """The paper's look-up-table softmax: 8-bit fixed-point in, 16-bit out."""

    input_bits: int = 8            # score precision entering the LUT (paper: 8)
    table_bits: int = 16           # exp table entry width (paper: 16)
    table_frac_bits: int = 15      # fixed point: Q1.15 for exp(x) in (0, 2)
    out_frac_bits: int = 16        # probability fixed point Q0.16
    # "paper":   table indexed by the raw int8 score byte (256 cases, §3.4)
    # "shifted": row max subtracted in the integer domain first (beyond-paper,
    #            numerically safe for long rows) — the default for model use.
    mode: str = "shifted"
    # logit scale: score byte b represents b * score_scale in real units
    score_scale: float = 1.0 / 16.0

    @property
    def table_size(self) -> int:
        return 1 << self.input_bits


# ---------------------------------------------------------------------------
# Model architecture configuration
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0           # routed experts = router outputs (0 = dense FFN)
    num_shared: int = 0            # always-on shared experts (DeepSeekMoE)
    top_k: int = 2
    capacity_factor: float = 1.25  # training's capacity routing only
    router_aux_weight: float = 0.01
    # renormalise the top-k gates to sum to 1 (DeepSeekMoE's
    # `norm_topk_prob`); False keeps the softmax probabilities as gates
    norm_topk_prob: bool = True
    # the routed experts this layer holds, as one chip's share of an
    # expert-parallel deployment: experts [first_held, first_held +
    # num_held) of the router's `num_experts` (num_held 0: all of them)
    num_held: int = 0
    first_held: int = 0

    @property
    def held(self) -> int:
        return self.num_held or self.num_experts

    @property
    def holds_share(self) -> bool:
        return self.held < self.num_experts


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"          # dense|moe|ssm|hybrid|audio|vlm
    num_layers: int = 4
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0              # 0 -> d_model // num_heads
    d_ff: int = 1024               # FFN width (MoE: each expert's)
    dense_d_ff: int = 0            # MoE dense prefix's FFN width (0 -> d_ff)
    vocab_size: int = 1024
    activation: str = "swiglu"     # swiglu|geglu|gelu|relu_sq
    norm: str = "rmsnorm"          # rmsnorm|layernorm
    qkv_bias: bool = False         # qwen2 style
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    pos: str = "rope"              # rope|absolute|none
    max_seq_len: int = 8192
    # attention structure
    attn_kind: str = "full"        # full|local|none
    window: int = 0                # local attention window (recurrentgemma: 2048)
    causal: bool = True
    # hybrid / ssm block pattern: sequence of block kinds repeated to num_layers
    # e.g. recurrentgemma: ("rglru", "rglru", "attn"); xlstm: 7x mlstm + 1 slstm
    block_pattern: Tuple[str, ...] = ("attn",)
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq_len: int = 0       # e.g. 1500 audio frames (stub frontend)
    # vlm stub frontend
    num_image_patches: int = 0
    # ssm / recurrent dims
    lru_width: int = 0             # RG-LRU state width (0 -> d_model)
    conv1d_width: int = 4
    num_dense_layers: int = 0      # leading non-MoE layers (deepseek-moe: 1)
    moe: MoEConfig = MoEConfig()
    attn_impl: str = "behavioral"  # behavioral|kernel (serve-path attention)
    # decode specialization of the kernel path: auto-select the
    # flash-decode kernel when a serve step has Sq == 1
    decode_kernel: bool = True
    decode_block_k: int = 256      # KV block size of the decode grid
    # KV-cache storage precision: 8 = int8 values (default, the paper's
    # layout), 4 = blockwise dynamic-map codes packed two per byte (halves
    # KV bytes/token; scale planes are the same absmax/127 grid either way).
    # Ring (sliding-window) caches always store int8 regardless.
    kv_bits: int = 8
    remat: str = "block"           # none|block — activation checkpointing
    # PIM integration
    pim: PIMConfig = PIMConfig()
    lut: LUTSoftmaxConfig = LUTSoftmaxConfig()
    # which parts run through the PIM behavioral model
    pim_linears: bool = True       # QKV/out/FFN projections via PIM quantized MVM
    pim_attention: bool = True     # int8 score + LUT softmax + int8 AV (serve path)
    # dtype policy
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.num_heads

    @property
    def resolved_dense_d_ff(self) -> int:
        return self.dense_d_ff or self.d_ff

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    def _layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind: a MoE stack's dense prefix ("dense"), then
        the pattern repeated (`transformer.pattern_layout`)."""
        pat = self.block_pattern
        n = self.num_dense_layers if "moe" in pat else 0
        rest = self.num_layers - n
        return ("dense",) * n + (pat * -(-rest // len(pat)))[:rest]

    def _ffn_params(self, width: int) -> int:
        glu = self.activation in ("swiglu", "geglu")
        return (3 if glu else 2) * self.d_model * width

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head).  A MoE
        layer counts its held experts (`MoEConfig.held`) and its router."""
        d, h = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        attn = d * h * n_q + 2 * d * h * n_kv + h * n_q * d
        ffn_dense = self._ffn_params(self.d_ff)
        per_layer = []
        for kind in self._layer_kinds():
            if kind == "dense":
                ffn = self._ffn_params(self.resolved_dense_d_ff)
                per_layer.append(attn + ffn + 2 * d)
            elif kind == "moe":
                ffn = (self.moe.held + self.moe.num_shared) * ffn_dense
                ffn += d * self.moe.num_experts  # router
                per_layer.append(attn + ffn + 2 * d)
            elif kind == "rglru":
                w = self.lru_width or d
                rec = 2 * d * w + w * d + self.conv1d_width * w + 2 * w
                per_layer.append(rec + ffn_dense + 2 * d)
            elif kind in ("mlstm", "slstm"):
                # xlstm-style block: qkv+gates+out ~ 4*d*d + 2*d*4*d up/down
                per_layer.append(4 * d * d + 2 * d * 4 * d + 2 * d)
            else:
                per_layer.append(attn + ffn_dense + 2 * d)
        total = sum(per_layer)
        total += self.vocab_size * d  # embedding
        if not self.tie_embeddings:
            total += self.vocab_size * d
        if self.is_encoder_decoder:
            enc_ffn = 2 * d * self.d_ff
            total += self.num_encoder_layers * (attn + enc_ffn + 2 * d)
            total += self.num_layers * (attn + 2 * d)  # cross attention
        return total

    def active_param_count(self) -> int:
        """Params active per token (MoE: only top_k + shared experts)."""
        if not self.moe.num_experts:
            return self.param_count()
        m = self.moe
        n_moe = self._layer_kinds().count("moe")
        ffn = self._ffn_params(self.d_ff)
        idle = n_moe * (m.held - min(m.top_k, m.held)) * ffn
        return self.param_count() - idle


def _pattern_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """Expand block_pattern to num_layers entries."""
    pat = cfg.block_pattern
    reps = -(-cfg.num_layers // len(pat))
    return (pat * reps)[: cfg.num_layers]


# ---------------------------------------------------------------------------
# Input-shape cells (assigned shapes)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# Mesh / runtime configuration
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    microbatches: int = 1          # gradient accumulation
    remat: str = "block"           # none|block|full
    grad_compression: str = "none" # none|int8_ef
    seed: int = 0
    checkpoint_every: int = 100
    checkpoint_dir: str = "/tmp/attentionlego_ckpt"
    keep_checkpoints: int = 3
