"""Shared building blocks: initializers, norms, RoPE, embeddings."""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=jnp.float32) -> jnp.ndarray:
    """Fan-in-scaled truncated-normal init (LeCun normal)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = fan_in ** -0.5
    return (std * jax.random.truncated_normal(key, -3.0, 3.0, shape)).astype(dtype)


def embed_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=jnp.float32) -> jnp.ndarray:
    return (0.02 * jax.random.normal(key, shape)).astype(dtype)


def split_keys(key: jax.Array, n: int):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(kind: str, dim: int) -> Params:
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(kind: str, params: Params, x: jnp.ndarray,
               eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + eps) * params["scale"]
    elif kind == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    else:
        raise ValueError(kind)
    return y.astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, s) -> jnp.ndarray:
    """YaRN inverse frequencies (``s``: a :class:`repro.config.YarnScaling`),
    as DeepSeek-V2 computes them: the extrapolated ``theta`` frequencies
    above the correction range of ``beta_fast``..``beta_slow`` rotations
    over the original context, the interpolated ones (divided by
    ``factor``) below it, and a linear ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(s.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(s.beta_fast)), 0)
    high = min(math.ceil(correction_dim(s.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inter = extra / np.float32(s.factor)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp          # 1 where the frequency is extrapolated
    return jnp.asarray(inter * (1 - keep) + extra * keep, jnp.float32)


def rope_tables(dim: int, theta: float, scaling=None
                ) -> Tuple[jnp.ndarray, float]:
    """Inverse frequencies of a rotary embedding over ``dim`` dims, and
    the factor on its cos/sin (1 without ``scaling``)."""
    if scaling is None:
        return rope_frequencies(dim, theta), 1.0
    return (yarn_frequencies(dim, theta, scaling),
            yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               inv_freq: jnp.ndarray, mscale: float = 1.0) -> jnp.ndarray:
    """Rotate-half rotary embedding: dim i pairs with i + D/2.
    x: (..., S, H, D); positions: broadcastable to (..., S); ``mscale``
    scales cos and sin (YaRN)."""
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (...,S,D/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------

def activation_fn(kind: str):
    if kind == "swiglu":
        # caller handles the gate/up split; this is the gate nonlinearity
        return jax.nn.silu
    if kind == "gelu":
        return jax.nn.gelu
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  valid_vocab: Optional[int] = None,
                  label_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean token cross-entropy; padded vocab columns masked to -inf."""
    logits = logits.astype(jnp.float32)
    if valid_vocab is not None and valid_vocab < logits.shape[-1]:
        col = jnp.arange(logits.shape[-1])
        logits = jnp.where(col < valid_vocab, logits, -1e9)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # masked reduction instead of take_along_axis: stays sharded over a
    # vocab-partitioned logits tensor (no all-gather), fuses to one pass
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.where(col == labels[..., None], logits, 0.0).sum(-1)
    nll = logz - gold
    if label_mask is not None:
        denom = jnp.maximum(label_mask.sum(), 1)
        return (nll * label_mask).sum() / denom
    return nll.mean()


def chunked_head_cross_entropy(x: jnp.ndarray, head_w: jnp.ndarray,
                               labels: jnp.ndarray, *,
                               valid_vocab: int,
                               chunk: int = 512) -> jnp.ndarray:
    """Fused LM head + cross-entropy, chunked over the sequence.

    Never materializes full (B, S, V) float32 logits: each sequence chunk
    computes logits -> CE inside a checkpointed scan step, so the backward
    pass recomputes per-chunk logits instead of saving them. This is the
    memory-dominant tensor of large-vocab training (EXPERIMENTS.md §Perf).
    x: (B, S, d); head_w: (d, V); labels: (B, S).
    """
    B, S, d = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = x.shape[1] // chunk
    xc = jnp.moveaxis(x.reshape(B, nc, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(acc, inp):
        xb, lb = inp
        logits = (xb @ head_w).astype(jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        if valid_vocab < logits.shape[-1]:
            logits = jnp.where(col < valid_vocab, logits, -1e9)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.where(col == lb[..., None], logits, 0.0).sum(-1)
        valid = (lb >= 0).astype(jnp.float32)
        nll = (logz - gold) * valid
        return (acc[0] + nll.sum(), acc[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                                 (xc, lc))
    return tot / jnp.maximum(cnt, 1.0)
