"""Per-kind transformer blocks (pre-norm residual) and their caches.

A block = sequence mixer + optional feed-forward, selected by
:class:`repro.config.LayerSpec`. Zamba-style ``shared_attn`` blocks read
their mixer (and companion FFN) weights from a single globally shared
parameter set passed separately, so scanning over blocks never stacks them.

The attention mixer runs under the name scope ``attention`` and the MoE
feed-forward under ``moe`` (``jax.named_scope``: the compiled ops'
``op_name`` carries them into the profiler's trace).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import LayerSpec, ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (attention_decode_step, attention_forward,
                                    init_attention, init_cache)
from repro.models.common import Params, init_norm, apply_norm, split_keys
from repro.models.mlp import init_mlp, mlp_forward
from repro.models.moe import init_moe, moe_forward

ATTN_MIXERS = ("attn", "swa", "shared_attn")


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_block(key: jax.Array, cfg: ModelConfig, spec: LayerSpec, *,
               cross_attention: bool = False,
               num_experts: Optional[int] = None) -> Params:
    ks = split_keys(key, 6)
    p: Params = {"norm1": init_norm(cfg.norm, cfg.d_model)}
    if spec.mixer == "attn" or spec.mixer == "swa":
        p["attn"] = init_attention(ks[0], cfg)
    elif spec.mixer == "mamba2":
        p["mamba2"] = ssm_mod.init_mamba2(ks[0], cfg)
    elif spec.mixer == "mlstm":
        p["mlstm"] = ssm_mod.init_mlstm(ks[0], cfg)
    elif spec.mixer == "slstm":
        p["slstm"] = ssm_mod.init_slstm(ks[0], cfg)
    elif spec.mixer == "shared_attn":
        pass  # weights live in the shared set
    else:
        raise ValueError(spec.mixer)
    if cross_attention:
        p["norm_cross"] = init_norm(cfg.norm, cfg.d_model)
        p["cross"] = init_attention(ks[1], cfg)
    if spec.ffn == "dense" and spec.mixer != "shared_attn":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
        p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.activation)
    elif spec.ffn == "dense" and spec.mixer == "shared_attn":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)   # FFN weights shared
    elif spec.ffn == "moe":
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
        p["moe"] = init_moe(ks[2], cfg, num_experts=num_experts)
    return p


def init_shared(key: jax.Array, cfg: ModelConfig) -> Params:
    """Globally shared zamba block weights (attention + FFN), if any."""
    if not any(s.mixer == "shared_attn" for s in cfg.pattern):
        return {}
    k1, k2 = split_keys(key, 2)
    shared: Params = {"attn": init_attention(k1, cfg)}
    if any(s.mixer == "shared_attn" and s.ffn == "dense"
           for s in cfg.pattern):
        shared["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation)
    return shared


def init_block_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     seq_len: int, *, cross_len: int = 0,
                     dtype=jnp.float32) -> Dict[str, Any]:
    cache: Dict[str, Any] = {}
    if spec.mixer in ATTN_MIXERS:
        window = cfg.sliding_window if spec.mixer == "swa" else 0
        cache["attn"] = init_cache(cfg, batch, seq_len, window=window,
                                   dtype=dtype)
    elif spec.mixer == "mamba2":
        cache["ssm"] = ssm_mod.init_mamba2_cache(cfg, batch, dtype)
    elif spec.mixer == "mlstm":
        cache["ssm"] = ssm_mod.init_mlstm_cache(cfg, batch, dtype)
    elif spec.mixer == "slstm":
        cache["ssm"] = ssm_mod.init_slstm_cache(cfg, batch, dtype)
    if cross_len > 0:
        shape = (batch, cross_len, cfg.num_kv_heads * cfg.resolved_head_dim)
        cache["cross"] = {"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)}
    return cache


# ---------------------------------------------------------------------------
# Forward (full sequence)
# ---------------------------------------------------------------------------

def block_forward(
    params: Params,
    shared: Params,
    cfg: ModelConfig,
    spec: LayerSpec,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray,
    enc_out: Optional[jnp.ndarray] = None,
    capture: bool = False,
    return_cache: bool = False,
    moe_ffn_fn=None,
    moe_layer_fn=None,
    moe_executor: str = "dense",
    moe_grouped_fn=None,
    moe_router_impl: str = "fused",
) -> Tuple[jnp.ndarray, Dict[str, Any], Dict[str, Any]]:
    """Returns (x, cache, captured). ``captured`` may hold attn_argmax /
    topk_idx / expert_counts / routing (the executor's RoutingSummary)
    for the paper's feature extraction and the serving telemetry."""
    cache: Dict[str, Any] = {}
    cap: Dict[str, Any] = {}
    h = apply_norm(cfg.norm, params["norm1"], x)

    if spec.mixer in ATTN_MIXERS:
        attn_p = shared["attn"] if spec.mixer == "shared_attn" else params["attn"]
        window = cfg.sliding_window if spec.mixer == "swa" else 0
        rope = cfg.rope_theta if cfg.pos_embed == "rope" else 0.0
        with jax.named_scope("attention"):
            y, kv, argmax = attention_forward(
                attn_p, cfg, h, positions=positions, causal=cfg.causal,
                window=window, rope_theta=rope, capture=capture)
        if return_cache:
            cache["attn"] = kv
        if capture and argmax is not None:
            cap["attn_argmax"] = argmax
    elif spec.mixer == "mamba2":
        y, st = ssm_mod.mamba2_forward(params["mamba2"], cfg, h)
        if return_cache:
            cache["ssm"] = st
    elif spec.mixer == "mlstm":
        y, st = ssm_mod.mlstm_forward(params["mlstm"], cfg, h)
        if return_cache:
            cache["ssm"] = st
    elif spec.mixer == "slstm":
        y, st = ssm_mod.slstm_forward(params["slstm"], cfg, h)
        if return_cache:
            cache["ssm"] = st
    else:
        raise ValueError(spec.mixer)
    x = x + y

    if enc_out is not None and "cross" in params:
        h = apply_norm(cfg.norm, params["norm_cross"], x)
        y, kv, _ = attention_forward(params["cross"], cfg, h,
                                     positions=positions, kv_x=enc_out)
        x = x + y
        if return_cache:
            cache["cross"] = kv

    if spec.ffn == "dense":
        h = apply_norm(cfg.norm, params["norm2"], x)
        mlp_p = shared["mlp"] if spec.mixer == "shared_attn" else params["mlp"]
        x = x + mlp_forward(mlp_p, h, cfg.activation)
    elif spec.ffn == "moe":
        h = apply_norm(cfg.norm, params["norm2"], x)
        with jax.named_scope("moe"):
            if moe_layer_fn is not None:    # e.g. expert-parallel shard_map
                y, aux = moe_layer_fn(params["moe"], cfg, h)
            else:
                y, aux = moe_forward(params["moe"], cfg, h, capture=capture,
                                     executor=moe_executor,
                                     expert_ffn_fn=moe_ffn_fn,
                                     grouped_ffn_fn=moe_grouped_fn,
                                     router_impl=moe_router_impl)
        x = x + y
        cap["lb_loss"] = aux["lb_loss"]
        cap["z_loss"] = aux["z_loss"]
        cap["expert_counts"] = aux["expert_counts"]
        if capture:
            cap["topk_idx"] = aux["topk_idx"]
            cap["topk_weight"] = aux["topk_weight"]
            if "routing" in aux:
                cap["routing"] = aux["routing"]
    return x, cache, cap


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------

def block_decode_step(
    params: Params,
    shared: Params,
    cfg: ModelConfig,
    spec: LayerSpec,
    x: jnp.ndarray,
    cache: Dict[str, Any],
    *,
    pos,
    layer,
    capture: bool = False,
    cross_valid=None,
    moe_ffn_fn=None,
    moe_layer_fn=None,
    moe_executor: str = "dense",
    moe_grouped_fn=None,
    moe_router_impl: str = "fused",
    dense_threshold: int = 4096,
    kv_len: Optional[int] = None,
    attn_backend: str = "jnp",
) -> Tuple[jnp.ndarray, Dict[str, Any], Dict[str, Any]]:
    """Returns (x, new_cache, captured). ``pos`` may be scalar or (B,).

    ``cache`` holds this block's own leaves (``ssm`` states, the ``cross``
    encoder K/V) and, for an attention mixer, under ``attn`` the k/v
    stacks of every block at its unit position, (num_blocks, B, T,
    n_kv·hd): :func:`attention_decode_step` writes them in place at
    ``layer`` and ``new_cache["attn"]`` returns the updated stacks.

    Under ``capture``, ``captured`` mirrors :func:`block_forward`'s capture
    dict for the single decoded token: ``attn_argmax`` (B, 1) and the MoE
    ``topk_idx``/``topk_weight`` (B, 1, k) — the serving engine's expert
    telemetry reads these. ``cross_valid`` masks encoder padding in
    cross-attention (scalar or per-row). ``kv_len`` / ``attn_backend``
    forward the serving engine's ragged-decode hint and attention
    realization to :func:`attention_decode_step`.
    """
    new_cache: Dict[str, Any] = {}
    cap: Dict[str, Any] = {}
    h = apply_norm(cfg.norm, params["norm1"], x)

    if spec.mixer in ATTN_MIXERS:
        attn_p = shared["attn"] if spec.mixer == "shared_attn" else params["attn"]
        window = cfg.sliding_window if spec.mixer == "swa" else 0
        rope = cfg.rope_theta if cfg.pos_embed == "rope" else 0.0
        with jax.named_scope("attention"):
            y, kv, argmax = attention_decode_step(
                attn_p, cfg, h, cache["attn"], pos=pos, layer=layer,
                causal=cfg.causal, window=window, rope_theta=rope,
                capture=capture,
                dense_threshold=dense_threshold, kv_len=kv_len,
                backend=attn_backend)
        new_cache["attn"] = kv
        if capture and argmax is not None:
            cap["attn_argmax"] = argmax
    elif spec.mixer == "mamba2":
        y, st = ssm_mod.mamba2_decode_step(params["mamba2"], cfg, h,
                                           cache["ssm"])
        new_cache["ssm"] = st
    elif spec.mixer == "mlstm":
        y, st = ssm_mod.mlstm_decode_step(params["mlstm"], cfg, h,
                                          cache["ssm"])
        new_cache["ssm"] = st
    elif spec.mixer == "slstm":
        y, st = ssm_mod.slstm_decode_step(params["slstm"], cfg, h,
                                          cache["ssm"])
        new_cache["ssm"] = st
    else:
        raise ValueError(spec.mixer)
    x = x + y

    if "cross" in cache:
        h = apply_norm(cfg.norm, params["norm_cross"], x)
        y, _, _ = attention_decode_step(params["cross"], cfg, h,
                                        cache["cross"], pos=pos, layer=layer,
                                        cross=True, valid_len=cross_valid)
        x = x + y
        new_cache["cross"] = cache["cross"]

    if spec.ffn == "dense":
        h = apply_norm(cfg.norm, params["norm2"], x)
        mlp_p = shared["mlp"] if spec.mixer == "shared_attn" else params["mlp"]
        x = x + mlp_forward(mlp_p, h, cfg.activation)
    elif spec.ffn == "moe":
        h = apply_norm(cfg.norm, params["norm2"], x)
        with jax.named_scope("moe"):
            if moe_layer_fn is not None:
                y, aux = moe_layer_fn(params["moe"], cfg, h)
            else:
                y, aux = moe_forward(params["moe"], cfg, h, capture=capture,
                                     executor=moe_executor,
                                     expert_ffn_fn=moe_ffn_fn,
                                     grouped_ffn_fn=moe_grouped_fn,
                                     router_impl=moe_router_impl)
        x = x + y
        if capture and "topk_idx" in aux:
            cap["topk_idx"] = aux["topk_idx"]
            cap["topk_weight"] = aux["topk_weight"]
            if "routing" in aux:
                cap["routing"] = aux["routing"]
    return x, new_cache, cap
