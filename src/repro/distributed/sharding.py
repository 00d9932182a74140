"""Sharding rules: param-name-based logical axes with divisibility-aware
fallback (DESIGN.md §7).

Tensor parallelism shards over the ``model`` mesh axis; batch shards over
``data`` (and ``pod`` when present). Any dimension that does not divide the
model-axis size is replicated instead — e.g. granite-34b's single KV head,
whisper's 12 attention heads, xlstm's 4 mLSTM heads.

The rules are keyed on parameter names (the model zoo uses a consistent
naming scheme), matched against the flattened pytree path.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig


def _div(n: int, k: int) -> bool:
    return n % k == 0


def batch_spec(mesh: Mesh, batch_size: int) -> Tuple:
    """Axes to shard a global-batch dimension over (pod+data), or None."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    total = int(np.prod([mesh.shape[a] for a in axes]))
    if batch_size % total == 0 and batch_size >= total:
        return axes if len(axes) > 1 else axes[0]
    # batch=1 long-context etc: cannot shard the batch
    return None


def _model_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def param_spec(cfg: ModelConfig, path: Tuple[str, ...], shape: Tuple[int, ...],
               mesh: Mesh) -> P:
    """PartitionSpec for one parameter, identified by its tree path."""
    ms = _model_size(mesh)
    name = path[-1]
    stacked = "blocks" in path        # leading num_blocks axis from scan
    parent = path[-2] if len(path) >= 2 else ""

    def lead(*spec):
        return P(None, *spec) if stacked else P(*spec)

    nh, nkv = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim

    # ---- embeddings / head
    if name == "embed":
        return P("model", None) if _div(shape[0], ms) else P()
    if name == "lm_head":
        return P(None, "model") if _div(shape[1], ms) else P()
    if name == "pos_table":
        return P()

    # ---- attention
    if name == "wq":
        ok = _div(nh, ms) and _div(shape[-1], ms)
        return lead(None, "model") if ok else lead(None, None)
    if name in ("wk", "wv"):
        ok = _div(nkv, ms) and _div(shape[-1], ms)
        return lead(None, "model") if ok else lead(None, None)
    if name == "wo":
        ok = _div(nh, ms) and _div(shape[-2], ms)
        return lead("model", None) if ok else lead(None, None)

    # ---- dense mlp
    if name in ("w_gate", "w_up", "w_in") and parent != "moe":
        if len(shape) - int(stacked) == 2:
            return lead(None, "model") if _div(shape[-1], ms) else lead(None, None)
    if name in ("w_down", "w_out") and parent != "moe":
        if len(shape) - int(stacked) == 2:
            return lead("model", None) if _div(shape[-2], ms) else lead(None, None)

    # ---- MoE experts: expert-parallel over 'model' (padded to divide)
    if parent == "moe" or (len(path) >= 3 and path[-3] == "moe"):
        if name == "router":
            return lead(None, None)
        if name in ("w_gate", "w_up", "w_in", "w_down", "w_out"):
            if len(shape) - int(stacked) == 3:    # (E, d, ff)
                return lead("model", None, None) if _div(shape[-3], ms) \
                    else lead(None, None, None)
            # shared-expert dense mats
            if name in ("w_down", "w_out"):
                return lead("model", None) if _div(shape[-2], ms) \
                    else lead(None, None)
            return lead(None, "model") if _div(shape[-1], ms) \
                else lead(None, None)

    # ---- mamba2
    if name in ("w_z", "w_x"):
        return lead(None, "model") if _div(shape[-1], ms) else lead(None, None)
    if name == "conv_w_x":
        return lead(None, "model") if _div(shape[-1], ms) else lead(None, None)
    if name == "conv_b_x":
        return lead("model") if _div(shape[-1], ms) else lead(None)
    if name == "out_proj":
        return lead("model", None) if _div(shape[-2], ms) else lead(None, None)
    if name in ("w_B", "w_C", "w_dt", "conv_w_B", "conv_w_C", "conv_b_B",
                "conv_b_C", "A_log", "D", "dt_bias", "b", "bi", "bf", "r"):
        return lead(*([None] * (len(shape) - int(stacked))))

    # ---- norms, small gates, everything else: replicate
    return lead(*([None] * (len(shape) - int(stacked))))


def param_shardings(cfg: ModelConfig, params_shape: Any,
                    mesh: Mesh) -> Any:
    """Tree of NamedShardings matching a params(-shaped) pytree."""
    flat = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    treedef = jax.tree_util.tree_structure(params_shape)
    specs = []
    for path, leaf in flat:
        names = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path)
        spec = param_spec(cfg, names, leaf.shape, mesh)
        specs.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, specs)


def zero1_shardings(cfg: ModelConfig, params_shape: Any,
                    mesh: Mesh) -> Any:
    """ZeRO-1: optimizer-state shardings = param shardings with the 'data'
    axis added on the first still-unsharded, divisible dimension. Cuts the
    f32 mu/nu residency by the data-parallel degree."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))
    daxis = data_axes if len(data_axes) > 1 else data_axes[0]
    base = param_shardings(cfg, params_shape, mesh)

    def extend(leaf, sh):
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        for i, (dim, s) in enumerate(zip(leaf.shape, spec)):
            if s is None and dim % dsize == 0 and dim >= dsize:
                spec[i] = daxis
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(extend, params_shape, base)


def cache_shardings(cfg: ModelConfig, cache_shape: Any, mesh: Mesh,
                    batch_size: int) -> Any:
    """Shardings for the decode cache.

    Attention K/V (nb, B, T, nkv*hd): batch over data when divisible; for
    global-attention caches with batch=1 (long_500k) the TIME axis shards
    over 'data' instead (sequence parallelism over the cache); the fused
    head axis over 'model' when the KV heads divide (each shard then holds
    whole heads). Recurrent states shard batch over data and the
    head/d_inner dim over 'model' when divisible.
    """
    ms = _model_size(mesh)
    bspec = batch_spec(mesh, batch_size)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in data_axes]))

    flat = jax.tree_util.tree_flatten_with_path(cache_shape)[0]
    treedef = jax.tree_util.tree_structure(cache_shape)
    specs = []
    for path, leaf in flat:
        names = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
        shape = leaf.shape
        if "attn" in names or "cross" in names:
            # (nb, B, T, nkv*hd)
            heads = "model" if _div(cfg.num_kv_heads, ms) else None
            if bspec is not None:
                spec = P(None, bspec, None, heads)
            elif _div(shape[2], dsize) and shape[2] >= dsize:
                seq_ax = data_axes if len(data_axes) > 1 else data_axes[0]
                spec = P(None, None, seq_ax, heads)
            else:
                spec = P(None, None, None, heads)
        elif names[-1] == "state" and len(shape) == 5:  # mamba (nb,B,H,P,N)
            h_ok = _div(shape[2], ms)
            spec = P(None, bspec, "model" if h_ok else None, None, None)
        else:
            spec = P(None, bspec, *([None] * (len(shape) - 2)))
        specs.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, specs)
