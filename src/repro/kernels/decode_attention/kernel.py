"""GQA flash-decode — Pallas TPU kernel.

One new query token attends over a (possibly 500k-slot) KV cache with an
online-softmax accumulator held in VMEM scratch. Grid = (batch, kv_head,
cache_block); the cache axis is the innermost, sequential dimension so the
(m, l, acc) scratch carries across cache blocks and the output is written
once on the last block.

This is the decode_32k / long_500k hot spot: entirely memory-bound
(one pass over the cache), so the block size (default 512 slots) is chosen
to keep the HBM->VMEM pipeline deep rather than to feed the MXU. The G
(q-heads-per-kv-head) x D tile uses the MXU for the (G, D) x (D, bt)
score matmul.

Slot-validity masking covers both linear caches (valid = pos+1) and
rolling sliding-window caches (valid = min(pos+1, window)) — keys are
rope'd before caching, so validity is the only masking needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(valid_ref, q_ref, k_ref, v_ref, out_ref,
                   m_scr, l_scr, acc_scr, *, block_t: int):
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bt, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (bt, D)
    D = q.shape[-1]
    scores = jnp.dot(q, k.T,
                     preferred_element_type=jnp.float32) * (D ** -0.5)
    slot = t * block_t + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    valid = valid_ref[pl.program_id(0)]
    scores = jnp.where(slot < valid, scores, NEG_INF)

    m_prev = m_scr[...]                            # (G, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(t == nt - 1)
    def _emit():
        out_ref[0, 0] = (acc_scr[...]
                         / jnp.maximum(l_scr[...], 1e-30)).astype(
                             out_ref.dtype)


def decode_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            valid_len: jnp.ndarray, *, block_t: int = 512,
                            interpret: bool) -> jnp.ndarray:
    """q: (B, N, G, D); k, v: head-major (B, N, T, D); valid_len: (B,)
    int32. Head-major keeps each KV block's last two dims (block_t, D)
    tileable by Mosaic."""
    B, N, G, D = q.shape
    T = k.shape[2]
    block_t = min(block_t, T)
    assert T % block_t == 0
    # valid_len rides in SMEM by scalar prefetch: index maps and the body
    # receive the whole (B,) vector
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, N, T // block_t),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, t, vl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_t, D),
                         lambda b, h, t, vl: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, D),
                         lambda b, h, t, vl: (b, h, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, t, vl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, block_t=block_t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N, G, D), q.dtype),
        interpret=interpret,
    )(valid_len, q, k, v)
