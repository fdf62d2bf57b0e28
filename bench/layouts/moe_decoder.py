"""How a DeepSeekMoE decoder maps onto the program, for configurations whose
`reference` is `moe_decoder`: MHA or GQA attention (RMSNorm, RoPE), the
first `first_k_dense_replace` layers a dense bias-free SwiGLU at
`intermediate_size`, every later layer a softmax router over
`router_experts` routed SwiGLU experts of `moe_intermediate_size`, top
`num_experts_per_tok`, beside `n_shared_experts` shared experts.

A configuration may be one chip's share of an expert-parallel deployment:
it holds `n_routed_experts` of the router's `router_experts`, from
`first_held_expert` on (the program's `MoEConfig.num_held`,
`first_held`).  The layout's functions are those of
`dense_decoder.py`: `program_config`, `WEIGHT_RULES`, `weight_views`,
`linear_work` and `attention_layers`.
"""
from __future__ import annotations

from typing import Tuple


def _fan_in(key, shape):
    import jax
    import jax.numpy as jnp
    return jax.random.normal(key, shape, jnp.float32) * (1.0 / shape[-2]) ** 0.5


# router (L, D, E), expert stacks (L, E, D, F) and (L, E, F, D)
WEIGHT_RULES = {"router": _fan_in, "w_gate": _fan_in, "w_in": _fan_in,
                "w_out": _fan_in}


def program_config(c: dict, **overrides):
    """The program's `ModelConfig` for a configuration file.  `overrides`
    replace program fields, as the control's lower precision does."""
    from repro.configs.base import ModelConfig, MoEConfig, PIMConfig

    prog = c["program"]
    if (c.get("hidden_act", "silu") != "silu" or c.get("bias", False)
            or c.get("scoring_func", "softmax") != "softmax"
            or c.get("moe_layer_freq", 1) != 1
            or float(c.get("rms_norm_eps", 1e-6)) != 1e-6):
        raise ValueError(f"{c['name']}: only bias-free SwiGLU decoders with "
                         "a softmax router on every layer past the dense "
                         "ones, and RMSNorm eps 1e-6, map onto the program")
    fields = dict(
        name=c["name"], family="moe",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        d_ff=int(c["moe_intermediate_size"]),
        dense_d_ff=int(c["intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        activation="swiglu", norm="rmsnorm",
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        rope_theta=float(c["rope_theta"]),
        max_seq_len=int(c["max_position_embeddings"]),
        block_pattern=("moe",),
        num_dense_layers=int(c["first_k_dense_replace"]),
        moe=MoEConfig(num_experts=int(c["router_experts"]),
                      num_shared=int(c["n_shared_experts"]),
                      top_k=int(c["num_experts_per_tok"]),
                      norm_topk_prob=bool(c["norm_topk_prob"]),
                      num_held=int(c["n_routed_experts"]),
                      first_held=int(c["first_held_expert"])),
        attn_impl=prog["attn_impl"],
        kv_bits=int(prog["kv_bits"]),
        param_dtype=prog["param_dtype"],
        compute_dtype=prog["compute_dtype"],
        pim=PIMConfig(**prog.get("pim", {})),
    )
    fields.update(overrides)
    return ModelConfig(**fields)


def _attention(block, take) -> dict:
    a = block["attn"]
    return {"norm1": take(block["norm1"]["scale"]),
            "norm2": take(block["norm2"]["scale"]),
            "wq": take(a["wq"]["w"]), "wk": take(a["wk"]["w"]),
            "wv": take(a["wv"]["w"]), "wo": take(a["wo"]["w"])}


def weight_views(params, c: dict) -> dict:
    """Layer i < first_k_dense_replace: the dense prefix's block, with
    `w_gate`, `w_in`, `w_out`; later layers: the stacked MoE blocks, with
    `router` (D, router_experts) and the held `experts` and the `shared`
    stacks by projection."""
    n_dense = int(c["first_k_dense_replace"])

    def layer(i: int) -> dict:
        if i < n_dense:
            block = params["dense_prefix"][i]
            mlp = block["mlp"]
            return dict(_attention(block, lambda x: x),
                        **{k: mlp[k]["w"] for k in ("w_gate", "w_in",
                                                    "w_out")})
        block, j = params["blocks"][0], i - n_dense
        moe = block["moe"]
        return dict(_attention(block, lambda x: x[j]),
                    router=moe["router"][j],
                    experts={k: v[j] for k, v in moe["experts"].items()},
                    shared={k: v[j] for k, v in moe["shared"].items()})

    return {"embed": params["embed"]["table"],
            "head": params["unembed"]["table"],
            "final_norm": params["final_norm"]["scale"],
            "layer": layer}


def linear_work(c: dict, tokens: int) -> Tuple[int, int]:
    """A lower bound: each token passes the attention weights, the dense
    layers' and each MoE layer's shared experts; a held routed expert is
    left out, as a token's top-k may all lie on other chips (the routed
    work is counted from the program's `moe.load` spans, by
    `moe_expert_roofline`).  The router (float, outside the PIM linears)
    is left out too."""
    d, dh = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    attn = d * dh * (2 * h + 2 * hkv)
    per_token = (c["num_hidden_layers"] * attn
                 + n_dense * 3 * d * c["intermediate_size"]
                 + n_moe * c["n_shared_experts"] * 3 * d
                 * c["moe_intermediate_size"])
    return 2 * per_token * tokens, per_token


def attention_layers(c: dict) -> int:
    return int(c["num_hidden_layers"])
