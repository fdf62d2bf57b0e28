"""A configuration file's sizes -> the program's ModelConfig, and the
benchmark's own weights for it, made on the device from the seed.

The benchmark makes the weights itself (never through the program's
`model.init`), so the reference that judges the served tokens takes nothing
the program made.  Only the tree's layout comes from the program: its leaf
names and shapes, read with `jax.eval_shape`.
"""
from __future__ import annotations

def model_config(c: dict, **overrides):
    """The program's `ModelConfig` for a configuration file (a dense GQA
    decoder with SwiGLU and RoPE).  `overrides` replace program fields, as
    the control's lower precision does."""
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


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (seeds may exceed 32 bits)."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def make_params(model, seed: int):
    """Every weight of `model`'s tree, drawn from `seed` in ONE jitted call
    on the default device, in the dtype the program holds (f32 masters):
    linear kernels N(0, 1/d_in), embedding and head tables N(0, 0.02^2),
    norm scales 1 + N(0, 0.1^2), biases 0."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [s for _, s in flat]

    def build(key):
        leaves = []
        for i, (name, s) in enumerate(zip(names, specs)):
            k = jax.random.fold_in(key, i)
            last = name.rsplit("/", 1)[-1]
            if last == "table":
                x = jax.random.normal(k, s.shape, jnp.float32) * 0.02
            elif last == "scale":
                x = 1.0 + 0.1 * jax.random.normal(k, s.shape, jnp.float32)
            elif last == "w":
                x = (jax.random.normal(k, s.shape, jnp.float32)
                     * (1.0 / s.shape[-2]) ** 0.5)
            elif last == "b":
                x = jnp.zeros(s.shape, jnp.float32)
            else:
                raise ValueError(f"no rule for weight leaf {name!r}")
            leaves.append(x.astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))


def layer_weights(params, layer: int) -> dict:
    """Plain per-layer views of the program's stacked tree, by the names
    the reference uses (`wq`, `wk`, `wv`, `wo`, `w_gate`, `w_in`, `w_out`,
    `norm1`, `norm2`)."""
    blocks = params["blocks"][0]
    attn, mlp = blocks["attn"], blocks["mlp"]
    return {
        "norm1": blocks["norm1"]["scale"][layer],
        "norm2": blocks["norm2"]["scale"][layer],
        "wq": attn["wq"]["w"][layer], "wk": attn["wk"]["w"][layer],
        "wv": attn["wv"]["w"][layer], "wo": attn["wo"]["w"][layer],
        "w_gate": mlp["w_gate"]["w"][layer], "w_in": mlp["w_in"]["w"][layer],
        "w_out": mlp["w_out"]["w"][layer],
    }
