"""Sharding rules and expert-parallel helpers.

Mesh axes (DESIGN.md §4):
  pod    outer data parallelism across pods        (multi-pod mesh only)
  data   FSDP: batch + parameter/optimizer shards
  model  tensor parallelism == the paper's spatial Lego tiling; also the
         expert-parallel axis for MoE archs

Parameter rule of thumb (FSDP x TP):
  attention/FFN projections: TP on the heads/ffn dim (model), FSDP on the
  other dim (data); expert stacks: EP on the expert dim (model), FSDP (data)
  on d_model; embeddings: vocab over model, d over data; everything tiny
  (norm scales, gates) replicated.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def current_mesh() -> Optional[Mesh]:
    """The ambient `with mesh:` context mesh, or None."""
    try:
        from jax._src.mesh import thread_resources
        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint against the ambient mesh; no-op without one.

    Axes that don't divide the corresponding mesh extent are dropped (so the
    same model code serves B=1 decode and B=256 train).  `spec` entries may
    be None, an axis name, or a tuple of axis names.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    fixed = []
    for dim, s in zip(x.shape, spec):
        if s is None:
            fixed.append(None)
            continue
        names = (s,) if isinstance(s, str) else tuple(s)
        names = tuple(n for n in names if n in mesh.axis_names)
        extent = 1
        for n in names:
            extent *= mesh.shape[n]
        if names and extent > 1 and dim % extent == 0:
            fixed.append(names if len(names) > 1 else names[0])
        else:
            fixed.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fixed)))


def dp_axes_spec() -> Tuple[str, ...]:
    """Batch axes of the ambient mesh ('pod','data' subset), for constrain."""
    mesh = current_mesh()
    if mesh is None:
        return ()
    return batch_axes(mesh)


# ---------------------------------------------------------------------------
# parameter partition specs (path-based rules)
# ---------------------------------------------------------------------------
def _param_spec(path: str, leaf: jax.Array, cfg: ModelConfig) -> P:
    nd = leaf.ndim
    stacked = path.startswith("blocks/") or path.startswith("enc_blocks")
    lead = (None,) if stacked else ()   # layer-stack axis is never sharded

    def spec(*axes):
        return P(*(lead + axes))

    body = nd - len(lead)
    if body <= 1:
        return spec(*([None] * body))
    # MoE expert stacks: (E, D, F) — EP over experts, FSDP over D
    if re.search(r"/(experts)/w_", path):
        return spec("model", "data", None)
    if re.search(r"/(shared)/w_", path):
        return spec(None, "data", "model")
    # embeddings: vocab over model, d over data
    if "embed/table" in path or "unembed/table" in path:
        return P("model", "data")
    if "pos_embed" in path:
        return P(None, "data")
    # attention / MLP projections (D_in, D_out):
    #   out-projections (wo, w_out, w_down): contract dim is sharded (model)
    if re.search(r"/(wo|w_out|w_down)/(w|w_q)$", path):
        return spec("model", "data")
    #   in-projections (wq/wk/wv/w_in/w_gate/w_up/...): output dim sharded
    if path.endswith("/w") or path.endswith("/w_q"):
        return spec("data", "model")
    # deployed per-channel weight scales: (1, d_out) — follow the output dim
    if path.endswith("/w_scale"):
        if re.search(r"/(wo|w_out|w_down)/w_scale$", path):
            return spec(None, "data")
        return spec(None, "model")
    # sLSTM square recurrences / RG-LRU gates: shard the output dim
    if re.search(r"/(w_z|w_i|w_f|w_o|w_input_gate|w_rec_gate|router)$", path):
        return spec("data", "model")
    if re.search(r"/r_[zifo]$", path):  # (H, dh, dh) block-diag recurrence
        return spec("model", None, None)
    return spec(*([None] * body))


def _tree_paths(tree) -> Any:
    """Map each leaf to its '/'-joined key path."""
    paths = []

    def visit(node, prefix):
        if isinstance(node, dict):
            for k in sorted(node):
                visit(node[k], prefix + (str(k),))
        elif isinstance(node, (tuple, list)):
            for i, v in enumerate(node):
                visit(v, prefix + (str(i),))
        else:
            paths.append("/".join(prefix))

    visit(tree, ())
    return paths


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec axes whose mesh extent doesn't divide the dim (uneven
    shards are legal in GSPMD but we keep shardings clean and predictable —
    e.g. whisper's vocab 51865 or xlstm's 4-head recurrence vs TP=16)."""
    fixed = []
    for i, s in enumerate(spec):
        if s is None or i >= len(shape):
            fixed.append(None if i < len(shape) else None)
            continue
        names = (s,) if isinstance(s, str) else tuple(s)
        extent = 1
        for n in names:
            extent *= mesh.shape.get(n, 1)
        fixed.append(s if extent > 1 and shape[i] % extent == 0 else None)
    while len(fixed) < len(shape):
        fixed.append(None)
    return P(*fixed)


def param_specs(params, cfg: ModelConfig, mesh: Optional[Mesh] = None):
    """PartitionSpec pytree matching `params` (path-based rules).

    With a mesh, specs are sanitized for divisibility per-leaf."""
    flat, treedef = jax.tree.flatten(params)
    paths = _tree_paths(params)
    assert len(paths) == len(flat)
    specs = [_param_spec(p, l, cfg) for p, l in zip(paths, flat)]
    if mesh is not None:
        specs = [_fit_spec(s, l.shape, mesh) for s, l in zip(specs, flat)]
    return jax.tree.unflatten(treedef, specs)


def param_shardings(params, cfg: ModelConfig, mesh: Mesh):
    specs = param_specs(params, cfg, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# activation / batch specs
# ---------------------------------------------------------------------------
def data_spec(mesh: Mesh) -> P:
    """Batch dim over all DP axes."""
    return P(batch_axes(mesh))


def cache_specs(cache, mesh: Mesh, global_batch: int) -> Any:
    """Serve-state PartitionSpecs.

    KV caches: batch over DP axes (when divisible); then kv-heads over
    `model` if divisible, else sequence, else head_dim (GQA kv counts often
    don't divide the TP width — seq-sharded KV is the flash-decoding-style
    fallback; reductions over the sharded axis become psums automatically).
    Recurrent states: batch over DP, widest trailing dim over model.

    Ragged serving metadata is REPLICATED, never DP-sharded: the (B,)
    per-slot `length` leaves and the scheduler's (B, max_pages) page-table
    leaves (dict keys "pages"/"page_table"/"seq_lens") carry page ids /
    fill levels that the host allocator and every replica's kernel
    scalar-prefetch must resolve identically — sharding the slot axis here
    is the multi-host scheduler work tracked in ROADMAP.md, not a spec
    decision.  Paged pools (`PagedKVCache`) have no batch axis at all and
    follow the same rule: kv-heads over `model` when divisible, else
    replicated.
    """
    ba = batch_axes(mesh)
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)

    from repro.core.attention import KVCache, PagedKVCache

    def spec_for(field: str, shape, stacked: bool = False) -> P:
        nd = len(shape)
        spec = [None] * nd
        # find the batch axis (first axis == global_batch; axis 0 of a
        # stacked leaf is the layer-repetition axis, never batch)
        b_ax = None
        for i, d in enumerate(shape):
            if i == 0 and stacked:
                continue
            if d == global_batch:
                b_ax = i
                break
        if b_ax is not None and global_batch % dp == 0 and dp > 1:
            spec[b_ax] = ba
        if field in ("k_q", "v_q"):            # (.., B, S, H, D)
            for cand in (nd - 2, nd - 3, nd - 1):
                if cand != b_ax and shape[cand] % tp == 0 and shape[cand] >= tp:
                    spec[cand] = "model"
                    break
        elif field in ("k_scale", "v_scale"):  # (.., B, S, H)
            for cand in (nd - 1, nd - 2):
                if cand != b_ax and shape[cand] % tp == 0 and shape[cand] >= tp:
                    spec[cand] = "model"
                    break
        elif field in ("length", "positions", "pages", "page_table",
                       "seq_lens"):
            # ragged (B,) lengths and (B, max_pages) page tables: always
            # replicated, even when a dim matches global_batch (the b_ax
            # DP spec computed above must NOT apply)
            return P(*([None] * nd))
        else:                                   # recurrent states
            for cand in range(nd - 1, -1, -1):
                if cand != b_ax and shape[cand] % tp == 0 and shape[cand] >= tp:
                    spec[cand] = "model"
                    break
        return P(*spec)

    def paged_spec_for(field: str, shape) -> P:
        # the page pool has NO batch axis (slots live in the page table) —
        # never DP-shard it; kv-heads over `model` when divisible, else
        # replicated (page ids must resolve locally on every DP replica)
        nd = len(shape)
        spec = [None] * nd
        h_ax = nd - 2 if field in ("k_q", "v_q") else nd - 1
        if tp > 1 and shape[h_ax] % tp == 0 and shape[h_ax] >= tp:
            spec[h_ax] = "model"
        return P(*spec)

    def visit(node, stacked=False):
        if isinstance(node, PagedKVCache):
            return PagedKVCache(*[
                paged_spec_for(f, getattr(node, f).shape)
                for f in node._fields])
        if isinstance(node, KVCache):
            return KVCache(*[
                spec_for(f, getattr(node, f).shape, stacked)
                for f in node._fields])
        if isinstance(node, dict):
            return {k: (spec_for(k, v.shape, stacked or k == "blocks")
                        if hasattr(v, "shape") and not isinstance(
                            v, (dict, tuple, list))
                        else visit(v, stacked or k == "blocks"))
                    for k, v in node.items()}
        if isinstance(node, (tuple, list)) and not hasattr(node, "shape"):
            return type(node)(visit(v, stacked) for v in node)
        return (spec_for("", node.shape, stacked)
                if hasattr(node, "shape") else P())

    return visit(cache)


def cache_shardings(cache, mesh: Mesh, global_batch: int):
    specs = cache_specs(cache, mesh, global_batch)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# expert-parallel MoE dispatch (shard_map over the model axis)
# ---------------------------------------------------------------------------
def moe_shard_map(params, xf: jax.Array, cfg: ModelConfig, mesh: Mesh):
    """Run moe_ffn_local under shard_map: tokens sharded over DP axes AND
    the model axis, experts over `model` (all_to_all dispatch).

    Tokens MUST be partitioned over the model axis too: with tokens only
    DP-sharded, all `model`-ranks route identical copies and the all_to_all
    delivers ep-many duplicates of every slot to each expert — a silent
    ep-fold compute redundancy (the 13x waste found in EXPERIMENTS.md §Perf
    cell 2).  Returns (y, aux).
    """
    from repro.models.moe import moe_ffn_local
    ba = batch_axes(mesh)
    ep = "model"
    token_axes = tuple(ba) + (ep,)
    tok_extent = 1
    for a in token_axes:
        tok_extent *= mesh.shape[a]
    tok_spec = P(token_axes, None) if xf.shape[0] % tok_extent == 0 \
        else P(ba, None)

    def pspec(path_leaf):
        path, leaf = path_leaf
        if "/experts/" in path or path.startswith("experts"):
            return P(ep, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    flat, treedef = jax.tree.flatten(params)
    paths = _tree_paths(params)
    in_param_specs = jax.tree.unflatten(
        treedef, [pspec(pl) for pl in zip(paths, flat)])

    reduce_axes = token_axes if tok_spec == P(token_axes, None) else ba

    def fn(p, x):
        y, aux = moe_ffn_local(p, x, cfg, ep_axis=ep)
        if reduce_axes:
            aux = jax.lax.pmean(aux, reduce_axes)
        return y, aux

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(in_param_specs, tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(params, xf)
