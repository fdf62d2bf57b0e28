"""The benchmark's own weights for a program model, made on the device from
the seed.

The benchmark makes the weights itself (never through the program's
`model.init`), so the reference that judges the served tokens takes nothing
the program made.  Only the tree's layout comes from the program: its leaf
names and shapes, read with `jax.eval_shape`.  How a configuration maps onto
the program, and how the reference reads the tree, is the cell's layout's
(`bench/layouts/<reference>.py`).
"""
from __future__ import annotations


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


def _generic_rules():
    """Linear kernels N(0, 1/d_in), embedding and head tables N(0, 0.02^2),
    norm scales 1 + N(0, 0.1^2), biases 0."""
    import jax
    import jax.numpy as jnp

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32)

    return {
        "table": lambda k, shape: normal(k, shape) * 0.02,
        "scale": lambda k, shape: 1.0 + 0.1 * normal(k, shape),
        "w": lambda k, shape: normal(k, shape) * (1.0 / shape[-2]) ** 0.5,
        "b": lambda k, shape: jnp.zeros(shape, jnp.float32),
    }


def make_params(model, seed: int, rules=None):
    """Every weight of `model`'s tree, drawn from `seed` in ONE jitted call
    on the default device, in the dtype the program holds (f32 masters).
    A leaf takes the rule named by the last part of its path: the generic
    rules, and `rules` (a layout's `WEIGHT_RULES`: name -> `rule(key,
    shape)` giving float32) beside them.  Leaf i draws from
    `fold_in(key, i)` in the tree's order."""
    import jax

    by_name = {**_generic_rules(), **(rules or {})}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaf_rules = []
    for path, _ in flat:
        name = _leaf_name(path)
        last = name.rsplit("/", 1)[-1]
        if last not in by_name:
            raise ValueError(f"no rule for weight leaf {name!r}")
        leaf_rules.append(by_name[last])
    specs = [s for _, s in flat]

    def build(key):
        leaves = [rule(jax.random.fold_in(key, i), s.shape).astype(s.dtype)
                  for i, (rule, s) in enumerate(zip(leaf_rules, specs))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
