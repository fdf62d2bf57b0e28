"""A DeepSeekMoE-style decoder on the program's `moe` block: leading dense
SwiGLU layers, then layers of a softmax router over routed SwiGLU experts
beside always-on shared experts.  The discovery test copies this file into
a benchmark root as `bench/layouts/tiny_moe.py`: a new architecture is new
files only.  The program renormalises the top-k gates and builds its dense
layers at the expert width, so configurations that state otherwise are
refused."""
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
    from repro.configs.base import ModelConfig, MoEConfig, PIMConfig

    prog = c["program"]
    if c["intermediate_size"] != c["moe_intermediate_size"]:
        raise ValueError(f"{c['name']}: the program builds its dense layers "
                         "at the expert width")
    if not c["norm_topk_prob"]:
        raise ValueError(f"{c['name']}: the program renormalises the top-k "
                         "gates")
    fields = dict(
        name=c["name"], family="moe",
        num_layers=int(c["num_hidden_layers"]),
        d_model=int(c["hidden_size"]),
        num_heads=int(c["num_attention_heads"]),
        num_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]),
        d_ff=int(c["moe_intermediate_size"]),
        vocab_size=int(c["vocab_size"]),
        activation="swiglu", norm="rmsnorm",
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        rope_theta=float(c["rope_theta"]),
        max_seq_len=int(c["max_position_embeddings"]),
        block_pattern=("moe",),
        num_dense_layers=int(c["first_k_dense_replace"]),
        moe=MoEConfig(num_experts=int(c["n_routed_experts"]),
                      num_shared=int(c["n_shared_experts"]),
                      top_k=int(c["num_experts_per_tok"])),
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
    `router` (D, E) and the `experts` and `shared` stacks by projection."""
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
    layers' and, in each MoE layer, its top-k and the shared experts'; the
    weights read once are those and, where the routing may send every token
    to the same experts, only top-k routed experts a layer.  The router
    (float, outside the PIM linears) is left out."""
    d, dh = c["hidden_size"], c["head_dim"]
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    attn = d * dh * (2 * h + 2 * hkv)
    expert = 3 * d * c["moe_intermediate_size"]
    per_token = (c["num_hidden_layers"] * attn
                 + n_dense * 3 * d * c["intermediate_size"]
                 + n_moe * (c["num_experts_per_tok"] + c["n_shared_experts"])
                 * expert)
    return 2 * per_token * tokens, per_token


def attention_layers(c: dict) -> int:
    return int(c["num_hidden_layers"])
