"""Training step builders: pjit + microbatched gradient accumulation.

Memory strategy for the big dry-run cells (DESIGN.md §4): parameters and
optimizer state are FSDP-sharded over (data, model); activations are bounded
by gradient accumulation — the per-microbatch activation footprint is
B_micro x S x D x L_boundaries, and the scan over microbatches overlaps each
microbatch's DP gradient reduction with the next one's backward pass (XLA
schedules the accumulation adds asynchronously).

`grad_compression="int8_ef"` swaps the implicit DP mean for an explicit int8
all-reduce with error feedback under shard_map (optim.compression).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, TrainConfig
from repro.models.model_zoo import Model
from repro.optim import adamw
from repro.runtime import sharding as sh


def _split_microbatches(batch: Dict[str, jax.Array], m: int):
    """(B, ...) -> (m, B/m, ...) per leaf."""
    def split(a):
        B = a.shape[0]
        assert B % m == 0, (B, m)
        return a.reshape(m, B // m, *a.shape[1:])
    return jax.tree.map(split, batch)


def grad_fn(model: Model):
    def fn(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        return grads, loss, metrics
    return fn


def make_train_step(model: Model, tcfg: TrainConfig,
                    mesh: Optional[Mesh] = None) -> Callable:
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics).

    With a mesh, inputs/outputs carry NamedShardings (FSDP x TP); without,
    it is a plain jit for CPU tests/examples.
    """
    gfn = grad_fn(model)
    m = tcfg.microbatches

    def accumulate(params, batch):
        if m == 1:
            grads, loss, metrics = gfn(params, batch)
            return grads, metrics
        mb = _split_microbatches(batch, m)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(carry, mb_i):
            acc, loss_acc = carry
            grads, loss, _ = gfn(params, mb_i)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / m,
                               acc, grads)
            return (acc, loss_acc + loss / m), None

        (grads, loss), _ = jax.lax.scan(body, (zero, jnp.float32(0.0)), mb)
        return grads, {"loss": loss}

    def step(params, opt_state, batch):
        if tcfg.grad_compression == "int8_ef" and mesh is not None:
            grads, residual, metrics = _compressed_grads(
                accumulate, params, batch, opt_state["residual"], mesh)
        else:
            grads, metrics = accumulate(params, batch)
            residual = None
        params, opt_state2, om = adamw.update(
            grads, {k: opt_state[k] for k in ("m", "v", "step")}, params, tcfg)
        new_state = dict(opt_state, **opt_state2)
        if residual is not None:
            new_state["residual"] = residual
        metrics = dict(metrics, **om)
        return params, new_state, metrics

    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1))

    if tcfg.grad_compression == "int8_ef":
        # pure-DP path: params replicated, explicit int8 collective inside
        return jax.jit(step, donate_argnums=(0, 1))

    cfg: ModelConfig = model.cfg
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sh.param_specs(params_shape, cfg, mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    opt_shape = jax.eval_shape(lambda p: init_opt_state(p, tcfg), params_shape)
    oshard = opt_shardings(opt_shape, pshard, mesh)
    bshard = NamedSharding(mesh, sh.data_spec(mesh))
    return jax.jit(
        step,
        in_shardings=(pshard, oshard, jax.tree.map(lambda _: bshard,
                                                   _abstract_batch_tree(cfg))),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1),
    )


def _abstract_batch_tree(cfg: ModelConfig):
    t = {"tokens": 0}
    if cfg.is_encoder_decoder:
        t["frames"] = 0
    if cfg.num_image_patches:
        t["image_embeds"] = 0
    return t


def init_opt_state(params, tcfg: TrainConfig):
    state = adamw.init(params)
    if tcfg.grad_compression == "int8_ef":
        from repro.optim import compression
        state["residual"] = compression.init_residual(params)
    return state


def opt_shardings(opt_shape, pshard, mesh: Mesh):
    """m/v/residual inherit the param shardings; step is replicated."""
    rep = NamedSharding(mesh, P())
    out = {}
    for k, v in opt_shape.items():
        if k == "step":
            out[k] = rep
        else:
            out[k] = pshard
    return out


def _compressed_grads(accumulate, params, batch, residual, mesh: Mesh):
    """Per-shard gradients + explicit int8/error-feedback DP all-reduce.

    The whole grad computation runs under shard_map over the DP axes (params
    replicated, batch sharded), so each shard holds a genuine partial
    gradient and the collective is the 4x-cheaper int8 reduce-scatter +
    all-gather from optim.compression.  Pure-DP scope: the compression path
    trades TP/FSDP for cheap DP collectives (EXPERIMENTS.md §Perf).
    """
    from repro.optim import compression
    ba = sh.batch_axes(mesh)
    if not ba:
        grads, metrics = accumulate(params, batch)
        return grads, residual, metrics
    def local(params, batch, residual):
        grads, metrics = accumulate(params, batch)
        g2, r2 = compression.allreduce_compressed(grads, residual, ba)
        loss = jax.lax.pmean(metrics["loss"], ba)
        return g2, r2, loss

    rep = jax.tree.map(lambda _: P(), params)
    bspec = jax.tree.map(lambda _: P(ba), batch)
    g2, r2, loss = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep, bspec, rep),
        out_specs=(rep, rep, P()),
        check_vma=False,
    )(params, batch, residual)
    return g2, r2, {"loss": loss}
