"""How a dense GQA decoder (RMSNorm, RoPE, bias-free SwiGLU) maps onto the
program, for configurations whose `reference` is `dense_decoder`.

A layout is the benchmark's one file per architecture that knows the
program's config and parameter tree:

    program_config(c, **overrides)  the program's `ModelConfig`
    WEIGHT_RULES                    init rules for leaves the generic rules
                                    of `bench/lib/model.make_params` lack
    weight_views(params, c)         the weights by the reference's names
    linear_work(c, tokens)          least (ops, bytes) of the layer stack's
                                    linears for one forward of `tokens`
    attention_layers(c)             layers that call the attention kernels
"""
from __future__ import annotations

from typing import Dict, Tuple

WEIGHT_RULES: Dict = {}


def program_config(c: dict, **overrides):
    """The program's `ModelConfig` for a configuration file.  `overrides`
    replace program fields, as the control's lower precision does."""
    from repro.configs.base import ModelConfig, PIMConfig

    prog = c["program"]
    if c.get("hidden_act", "silu") != "silu" or c.get("bias", False):
        raise ValueError(f"{c['name']}: only bias-free SwiGLU decoders map "
                         "onto the program's dense block")
    fields = dict(
        name=c["name"], family="dense",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        activation="swiglu", norm="rmsnorm",
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        rope_theta=float(c["rope_theta"]),
        max_seq_len=int(c["max_position_embeddings"]),
        block_pattern=("attn",),
        attn_impl=prog["attn_impl"],
        kv_bits=int(prog["kv_bits"]),
        param_dtype=prog["param_dtype"],
        compute_dtype=prog["compute_dtype"],
        pim=PIMConfig(**prog.get("pim", {})),
    )
    fields.update(overrides)
    return ModelConfig(**fields)


def weight_views(params, c: dict) -> dict:
    """`embed`, `head`, `final_norm` and `layer(i)`: layer i's plain views
    of the program's stacked tree (`wq`, `wk`, `wv`, `wo`, `w_gate`,
    `w_in`, `w_out`, `norm1`, `norm2`)."""
    blocks = params["blocks"][0]
    attn, mlp = blocks["attn"], blocks["mlp"]

    def layer(i: int) -> dict:
        return {
            "norm1": blocks["norm1"]["scale"][i],
            "norm2": blocks["norm2"]["scale"][i],
            "wq": attn["wq"]["w"][i], "wk": attn["wk"]["w"][i],
            "wv": attn["wv"]["w"][i], "wo": attn["wo"]["w"][i],
            "w_gate": mlp["w_gate"]["w"][i], "w_in": mlp["w_in"]["w"][i],
            "w_out": mlp["w_out"]["w"][i],
        }

    return {"embed": params["embed"]["table"],
            "head": params["unembed"]["table"],
            "final_norm": params["final_norm"]["scale"],
            "layer": layer}


def matmul_params(c: dict) -> int:
    """Weights of every per-token matmul of the layer stack (q, k, v, o
    and the three SwiGLU projections); the vocabulary head is apart."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, hkv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    per_layer = d * dh * (2 * h + 2 * hkv) + 3 * d * f
    return c["num_hidden_layers"] * per_layer


def linear_work(c: dict, tokens: int) -> Tuple[int, int]:
    """Every token passes every weight (2 ops a weight), and the int8
    weights are read once a forward (1 byte each)."""
    w = matmul_params(c)
    return 2 * w * tokens, w


def attention_layers(c: dict) -> int:
    return int(c["num_hidden_layers"])
