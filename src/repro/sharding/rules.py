"""Sharding rules: param-path pattern -> PartitionSpec, with divisibility
fallbacks.

Strategy (baseline, see EXPERIMENTS.md §Perf for hillclimbed variants):

* 2D logical layout per weight matrix — FSDP shard along the ``data``
  axis and tensor-parallel shard along the ``model`` axis:
    in-projections  (D, X):     P("data", "model")
    out-projections (X, D):     P("model", "data")
    embedding       (V, D):     P("model", "data")   (vocab-parallel)
    experts         (E, D, F):  P("model", "data", None)  (expert-parallel)
* Stacked layer params carry a leading L dim -> specs shift right one.
* The ``pod`` axis replicates params (pure DP across pods); the batch is
  sharded over ("pod", "data").
* Any dim not divisible by its mesh-axis extent falls back to
  unsharded on that axis (GQA head counts, odd vocab, tiny models) —
  compilation must succeed for every assigned arch on the production
  mesh, so the rules degrade rather than fail.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (path regex, spec WITHOUT the stacked-layer dim). Longest match wins.
_RULES: tuple[tuple[str, tuple], ...] = (
    # embeddings / lm head (tied), untied lm head (D, V)
    (r"embed/table$", ("model", "data")),
    (r"lm_head/w$", ("data", "model")),
    # attention
    (r"(attn|self_attn|cross_attn)/wq$", ("data", "model")),
    (r"(attn|self_attn|cross_attn)/wk$", ("data", "model")),
    (r"(attn|self_attn|cross_attn)/wv$", ("data", "model")),
    (r"(attn|self_attn|cross_attn)/wo$", ("model", "data")),
    # dense mlp
    (r"mlp/w_gate$", ("data", "model")),
    (r"mlp/w_up$", ("data", "model")),
    (r"mlp/w_down$", ("model", "data")),
    # moe (expert-parallel on model axis)
    (r"moe/w_router$", ("data", None)),
    (r"moe/w_gate$", ("model", "data", None)),
    (r"moe/w_up$", ("model", "data", None)),
    (r"moe/w_down$", ("model", None, "data")),
    # mamba2
    (r"mamba/w_in$", ("data", "model")),
    (r"mamba/w_out$", ("model", "data")),
    (r"mamba/conv_w$", (None, "model")),
    # xlstm
    (r"cell/w_up$", ("data", "model")),
    (r"cell/w[qkv]$", ("data", "model")),
    (r"cell/w_if$", ("data", None)),
    (r"cell/w_down$", ("model", "data")),
    (r"cell/w_x$", ("data", "model")),
    (r"cell/w_h$", ("model", None, None)),
    (r"cell/w_out$", ("data", "model")),
)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _fit(spec: tuple, shape: tuple, mesh: Mesh) -> P:
    """Drop axes whose extent does not divide the corresponding dim."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(ax if dim % size == 0 else None)
    # pad to rank
    out += [None] * (len(shape) - len(out))
    return P(*out)


def param_spec(path, leaf_shape, mesh: Mesh, *, stacked_depth: int = 0) -> P:
    """PartitionSpec for one param leaf.

    stacked_depth: how many leading dims are layer-stacking dims (scanned
    stacks have 1). Detected automatically by the caller from path names.

    MoE expert weights whose expert count does not divide the `model`
    axis (e.g. grok's 8 experts on a 16-wide axis) fall back to sharding
    the FFN dim on `model` instead of replicating: a replicated expert
    tensor makes GSPMD compute every expert redundantly on all 16 model
    shards (measured 16x useful-FLOP blowup — EXPERIMENTS.md §Perf).
    """
    name = _path_str(path)
    moe = re.search(r"moe/w_(gate|up|down)$", name)
    if moe:
        experts = leaf_shape[stacked_depth]
        model = mesh.shape.get("model", 1)
        if experts % model != 0:
            if moe.group(1) == "down":  # (E, F, D)
                spec = (None, "model", "data")
            else:  # (E, D, F)
                spec = (None, "data", "model")
            full = (None,) * stacked_depth + spec
            return _fit(full, leaf_shape, mesh)
    for pat, spec in _RULES:
        if re.search(pat, name):
            full = (None,) * stacked_depth + tuple(spec)
            return _fit(full, leaf_shape, mesh)
    return _fit((None,) * len(leaf_shape), leaf_shape, mesh)  # replicated


_STACKED_CONTAINERS = ("blocks", "encoder")
_UNSTACKED = ("shared_attn",)  # hybrid shared block is NOT stacked


def _is_stacked(path) -> bool:
    name_parts = []
    for p in path:
        if hasattr(p, "key"):
            name_parts.append(str(p.key))
    if not name_parts:
        return False
    if name_parts[0] in _UNSTACKED:
        return False
    # python-list blocks (ssm family) index with SequenceKey -> not stacked
    for p in path:
        if hasattr(p, "idx"):
            return False
    return name_parts[0] in _STACKED_CONTAINERS


def make_param_sharding(mesh: Mesh, params_shape, *, strategy: str = "2d") -> object:
    """Tree of NamedSharding matching a params (or opt-state) shape tree.

    strategy:
      "2d"         — FSDP on `data` + TP on `model` (baseline).
      "replicated" — pure data parallelism: params replicated, batch
                     sharded over BOTH data axes. For small archs this
                     removes every per-layer weight all-gather (§Perf).
    """

    def one(path, leaf):
        if strategy == "replicated":
            return NamedSharding(mesh, P(*([None] * len(leaf.shape))))
        depth = 1 if _is_stacked(path) else 0
        return NamedSharding(mesh, param_spec(path, leaf.shape, mesh, stacked_depth=depth))

    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_specs(mesh: Mesh, global_batch: int, *, include_model: bool = False) -> P:
    """Token batches shard over every data-like axis that divides B."""
    names = ("pod", "data", "model") if include_model else ("pod", "data")
    axes = [a for a in names if a in mesh.shape]
    size = int(np.prod([mesh.shape[a] for a in axes]))
    while axes and global_batch % size != 0:
        axes.pop(0)
        size = int(np.prod([mesh.shape[a] for a in axes]))
    if not axes:
        return P(None, None)
    return P(tuple(axes), None)


def make_batch_sharding(mesh: Mesh, batch_shape_tree, *,
                        include_model: bool = False) -> object:
    """Sharding tree for {"tokens","labels",("extras")} ShapeDtypeStructs."""

    def one(path, leaf):
        b = leaf.shape[0]
        spec = batch_specs(mesh, b, include_model=include_model)
        full = tuple(spec) + (None,) * (len(leaf.shape) - len(tuple(spec)))
        return NamedSharding(mesh, _fit(full, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, batch_shape_tree)


def cache_spec(path, leaf_shape, mesh: Mesh) -> P:
    """Decode caches: batch on data axes, heads/features on model.

    kv k/v: (L, B, S, KV, hd) -> (None, data, None, model, None)
    ssm state: (L, B, H, N, P) -> (None, data, model, None, None)
    everything else: batch-sharded on dim of size B where possible.
    """
    name = _path_str(path)
    if re.search(r"kv/(k|v)$", name):
        # Prefer KV-head sharding on "model"; GQA counts that don't divide
        # the axis fall back to sharding the cache SEQ dim instead (the
        # decode softmax then reduces over a sharded axis — GSPMD inserts
        # the all-reduce; still far cheaper than replicating the cache).
        kv_heads, seq = leaf_shape[3], leaf_shape[2]
        model = mesh.shape.get("model", 1)
        if kv_heads % model == 0:
            return _fit((None, "data", None, "model", None), leaf_shape, mesh)
        if seq % model == 0:
            return _fit((None, "data", "model", None, None), leaf_shape, mesh)
        return _fit((None, "data", None, None, None), leaf_shape, mesh)
    if re.search(r"kv/(k|v)_scale$", name):  # (L, B, S, KV)
        kv_heads, seq = leaf_shape[3], leaf_shape[2]
        model = mesh.shape.get("model", 1)
        if kv_heads % model == 0:
            return _fit((None, "data", None, "model"), leaf_shape, mesh)
        if seq % model == 0:
            return _fit((None, "data", "model", None), leaf_shape, mesh)
        return _fit((None, "data", None, None), leaf_shape, mesh)
    if re.search(r"kv/pos$", name):
        return P(*([None] * len(leaf_shape)))
    if re.search(r"^ssm$", name) or re.search(r"/ssm$", name):
        return _fit((None, "data", "model", None, None), leaf_shape, mesh)
    if re.search(r"conv$", name):
        return _fit((None, "data", None, None), leaf_shape, mesh)
    if re.search(r"enc_out$", name):
        return _fit(("data", None, None), leaf_shape, mesh)
    # xlstm states: (B, H, ...) batch on data
    return _fit(("data",) + (None,) * (len(leaf_shape) - 1), leaf_shape, mesh)


def make_cache_sharding(mesh: Mesh, cache_shape_tree) -> object:
    def one(path, leaf):
        return NamedSharding(mesh, cache_spec(path, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, cache_shape_tree)
