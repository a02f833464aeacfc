"""Named sharding rules: parameter/optimizer/batch/cache PartitionSpecs.

Scheme (single pod (data, model); multi-pod adds a leading "pod" axis that
joins the data-parallel group):
  * TP over "model": attention heads / FFN hidden / experts / vocab.
  * FSDP over "data" (optional, rc.fsdp): the non-TP dim of every large
    weight is sharded over the data axis; XLA inserts the all-gathers.
  * Batch over ("pod","data"); decode KV caches shard sequence over
    "model" (flash-decoding style) and batch over "data".

Rules match on (leaf name, ndim) — stacked layer params carry a leading
period dimension that is never sharded.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, RunConfig


def batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _param_rule(name: str, ndim: int, cfg: ModelConfig, rc: RunConfig,
                parent: str) -> P:
    fsdp = "data" if rc.fsdp else None
    tp = "model"
    ep_ok = cfg.moe and cfg.moe.n_experts % 16 == 0

    # --- embeddings / heads ---
    if name == "embed":
        return P(None, tp, fsdp) if ndim == 3 else P(tp, fsdp)
    if name == "lm_head":
        return P(None, fsdp, tp) if ndim == 3 else P(fsdp, tp)

    # --- MoE expert banks: 4D (period, E, in, out) ---
    if ndim == 4 and name in ("w_gate", "w_up", "w_down"):
        if ep_ok:
            return P(None, tp, fsdp, None)          # expert parallel
        if name == "w_down":
            return P(None, None, tp, fsdp)          # TP inside expert
        return P(None, None, fsdp, tp)
    if name == "router":
        return P(None, None, None)

    # --- column-parallel (d -> hidden) ---
    if name in ("wq", "wk", "wv", "w_gate", "w_up", "w_z", "w_x", "w_dt"):
        return P(None, fsdp, tp)
    # --- row-parallel (hidden -> d) ---
    if name in ("wo", "w_down", "out_proj"):
        return P(None, tp, fsdp)
    # --- small replicated projections ---
    if name in ("w_B", "w_C"):
        return P(None, fsdp, None)
    if name in ("conv_x",):
        return P(None, None, tp)
    if name in ("conv_B", "conv_C"):
        return P(None, None, None)
    # --- vectors ---
    if name in ("bq", "bk", "bv", "norm_scale", "conv_x_bias"):
        return P(None, tp)
    if name in ("A_log", "dt_bias", "D"):
        return P(None, tp)
    if name == "scale":      # rmsnorm over d_model (replicated activations)
        return P(*([None] * ndim))
    return P(*([None] * ndim))


def _path_names(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return out


def param_specs(tree_shapes, cfg: ModelConfig, rc: RunConfig):
    """PartitionSpec tree for a params (or optimizer-state) shape tree.

    Optimizer moments nest the param path (m/..., v/.../vr): the rule key
    is the innermost *weight* name on the path; adafactor's factored vr/vc
    drop the corresponding trailing dims of the parent spec.
    """
    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1]
        factored = None
        if name in ("vr", "vc") and len(names) >= 2:
            factored, name = name, names[-2]
        ndim = leaf.ndim + (1 if factored else 0)
        spec = _param_rule(name, ndim, cfg, rc, names[-2] if
                           len(names) >= 2 else "")
        if factored == "vr":      # parent spec minus last dim
            spec = P(*spec[:-1])
        elif factored == "vc":    # parent spec minus second-to-last dim
            spec = P(*(spec[:-2] + spec[-1:]))
        if len(spec) != leaf.ndim:
            # scalars (step) and anything unmatched: replicate
            spec = P(*([None] * leaf.ndim))
        return spec

    return jax.tree_util.tree_map_with_path(spec_for, tree_shapes)


def batch_specs(tree_shapes, mesh: Mesh):
    """Shard every batch leaf's leading dim over (pod, data)."""
    ba = batch_axes(mesh)

    def spec_for(path, leaf):
        if leaf.ndim == 0:
            return P()
        if leaf.shape[0] == 1:      # un-shardable singleton batch
            return P(*([None] * leaf.ndim))
        return P(ba, *([None] * (leaf.ndim - 1)))
    return jax.tree_util.tree_map_with_path(spec_for, tree_shapes)


def cache_specs(tree_shapes, mesh: Mesh, cfg: ModelConfig,
                seq_shard: bool = True):
    """KV/state cache specs: (period, batch, S, kv, dh) — batch over
    "data", sequence over "model" (flash-decoding SP) when batch alone
    cannot saturate the mesh; mamba states shard heads over "model"."""
    ba_all = batch_axes(mesh)        # ("pod","data") on the multi-pod mesh

    def _baxis(b: int):
        """Largest batch-axis tuple that divides the cache batch."""
        axes = list(ba_all)
        while axes:
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            if b % total == 0:
                return tuple(axes) if len(axes) > 1 else axes[0]
            axes.pop(0)              # drop "pod" first
        return None

    def spec_for(path, leaf):
        names = _path_names(path)
        name = names[-1]
        if name in ("k", "v", "k_scale", "v_scale"):
            baxis = _baxis(leaf.shape[1])
            saxis = "model" if seq_shard else None
            rest = [None] * (leaf.ndim - 3)
            return P(None, baxis, saxis, *rest)
        if name == "state":         # (period, b, nh, n, p)
            return P(None, _baxis(leaf.shape[1]), "model", None, None)
        if name == "conv":          # (period, b, k-1, channels)
            return P(None, _baxis(leaf.shape[1]), None, None)
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(spec_for, tree_shapes)


def legalize(spec_tree, shape_tree, mesh: Mesh):
    """Drop mesh axes from any spec dim that does not divide the global
    dim size (pjit argument shardings require exact divisibility; e.g.
    mamba2's vocab 50280 cannot shard 16-way and falls back to
    replicated-on-that-dim)."""
    def fix(spec, leaf):
        dims = list(spec) + [None] * (leaf.ndim - len(spec))
        out = []
        for size, ax in zip(leaf.shape, dims):
            if ax is None:
                out.append(None)
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            out.append(ax if size % total == 0 else None)
        return P(*out)
    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def to_named(mesh: Mesh, spec_tree, shape_tree=None):
    if shape_tree is not None:
        spec_tree = legalize(spec_tree, shape_tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
