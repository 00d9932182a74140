"""Grouped-query attention with KV caching, sliding windows, qk-norm.

Two compute paths:

* dense  -- materializes the score matrix; used for short sequences and when
            attention capture (the paper's attention-ID feature) is requested.
* flash  -- blocked online-softmax (lax.scan over KV chunks, q chunked via
            reshape) so long-context shapes have a bounded working set. This
            is the pure-jnp twin of ``repro.kernels.decode_attention``.

Shapes: x (B, S, d); K/V caches (B, T, n_kv·hd), heads and head dim
fused into the minor axis so no (n_kv, hd) tile pads them; decode reads
and writes them stacked over the model's blocks, (num_blocks, B, T,
n_kv·hd), and views the rows it scores as (B, T, n_kv, hd). GQA is
computed grouped (q reshaped to (B, S, n_kv, group, hd)) -- no KV head
repetition.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.common import (Params, apply_norm, apply_rope, dense_init,
                                 init_norm, rope_frequencies, split_keys)

NEG_INF = -1e9


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, cfg: ModelConfig, *,
                   num_heads: Optional[int] = None,
                   num_kv_heads: Optional[int] = None) -> Params:
    nh = num_heads or cfg.num_heads
    nkv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    d = cfg.d_model
    ks = split_keys(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, nh * hd)),
        "wk": dense_init(ks[1], (d, nkv * hd)),
        "wv": dense_init(ks[2], (d, nkv * hd)),
        "wo": dense_init(ks[3], (nh * hd, d)),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm("rmsnorm", hd)
        p["k_norm"] = init_norm("rmsnorm", hd)
    return p


# ---------------------------------------------------------------------------
# Dense attention (short sequences / capture path)
# ---------------------------------------------------------------------------

def _dense_attend(q, k, v, mask, *, capture: bool = False):
    """q: (B,N,G,S,D); k,v: (B,N,T,D); mask additive (S,T) or (B,1,1,S,T)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bngsd,bntd->bngst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bngst,bntd->bngsd", probs, v.astype(jnp.float32))
    attn_argmax = None
    if capture:
        # paper §III-B: per query token, the key position with the highest
        # summed softmax score across all heads -> attention ID.
        summed = probs.sum(axis=(1, 2))              # (B, S, T)
        attn_argmax = jnp.argmax(summed, axis=-1)    # (B, S)
    return out, attn_argmax


# ---------------------------------------------------------------------------
# Flash attention (blocked online softmax, pure jnp)
# ---------------------------------------------------------------------------

def _flash_attend(q, k, v, *, causal: bool, window: int, q_offset,
                  kv_valid_len=None, q_chunk: int = 512,
                  kv_chunk: int = 1024):
    """Blocked attention. q: (B,N,G,S,D); k,v: (B,N,T,D).

    ``q_offset``: absolute position of q[..., 0, :] (scalar, may be traced).
    ``kv_valid_len``: number of valid cache slots for decode — scalar, or
    (B,) for per-slot validity in the continuous-batching engine.
    Rectangular schedule: causal/window masking is applied, not skipped
    (2x FLOP overcount for causal prefill -- recorded in the roofline notes).
    """
    B, N, G, S, D = q.shape
    T = k.shape[2]
    scale = D ** -0.5
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    nq = -(-S // q_chunk)
    nk = -(-T // kv_chunk)
    S_pad, T_pad = nq * q_chunk, nk * kv_chunk
    if S_pad != S:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, S_pad - S), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0),) * 2 + ((0, T_pad - T), (0, 0)))
        v = jnp.pad(v, ((0, 0),) * 2 + ((0, T_pad - T), (0, 0)))
    # (nq, B, N, G, Cq, D)
    qc = jnp.moveaxis(q.reshape(B, N, G, nq, q_chunk, D), 3, 0)
    kc = jnp.moveaxis(k.reshape(B, N, nk, kv_chunk, D), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, N, nk, kv_chunk, D), 2, 0)
    valid_t = kv_valid_len if kv_valid_len is not None else T

    def q_body(qi_q):
        qi, qblk = qi_q
        qpos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        @jax.checkpoint   # don't save per-chunk score matrices in backward
        def kv_body(carry, kj_blk):
            m, l, acc = carry
            kj, kblk, vblk = kj_blk
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bngsd,bntd->bngst", qblk.astype(jnp.float32),
                           kblk.astype(jnp.float32)) * scale
            if jnp.ndim(valid_t) == 1:     # per-batch-row validity
                msk = (kpos[None, :] < valid_t[:, None])[:, None, None, None]
            else:
                msk = kpos[None, :] < valid_t
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window > 0:
                msk &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bngst,bntd->bngsd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, N, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, N, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, N, G, q_chunk, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (jnp.arange(nk), kc, vc))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(jax.checkpoint(q_body),
                      (jnp.arange(nq), qc))              # (nq,B,N,G,Cq,D)
    out = jnp.moveaxis(out, 0, 3).reshape(B, N, G, S_pad, D)
    return out[:, :, :, :S], None


# ---------------------------------------------------------------------------
# Public forward
# ---------------------------------------------------------------------------

def _project_qkv(params: Params, cfg: ModelConfig, x, kv_x,
                 nh: int, nkv: int):
    hd = cfg.resolved_head_dim
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, nh, hd)
    k = (kv_x @ params["wk"]).reshape(B, kv_x.shape[1], nkv, hd)
    v = (kv_x @ params["wv"]).reshape(B, kv_x.shape[1], nkv, hd)
    if "q_norm" in params:
        q = apply_norm("rmsnorm", params["q_norm"], q)
        k = apply_norm("rmsnorm", params["k_norm"], k)
    return q, k, v


def attention_forward(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    rope_theta: float = 0.0,
    capture: bool = False,
    num_heads: Optional[int] = None,
    num_kv_heads: Optional[int] = None,
    kv_x: Optional[jnp.ndarray] = None,         # cross-attention source
    flash_threshold: int = 2048,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Optional[jnp.ndarray]]:
    """Full-sequence attention. Returns (y, cache_kv, attn_argmax).

    ``cache_kv`` holds the rope'd K/V to seed decoding, (B, T, n_kv·hd):
    for windowed layers it is the rolling last-``window`` slice, otherwise
    the full sequence.
    """
    nh = num_heads or cfg.num_heads
    nkv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    B, S, _ = x.shape
    cross = kv_x is not None
    src = kv_x if cross else x
    q, k, v = _project_qkv(params, cfg, x, src, nh, nkv)
    if rope_theta > 0 and not cross:
        inv = rope_frequencies(hd, rope_theta)
        q = apply_rope(q, positions, inv)
        k = apply_rope(k, positions, inv)
    T = src.shape[1]
    g = nh // nkv
    qg = jnp.moveaxis(q.reshape(B, S, nkv, g, hd), 1, 3)   # (B,N,G,S,D)
    kt = jnp.moveaxis(k, 1, 2)                             # (B,N,T,D)
    vt = jnp.moveaxis(v, 1, 2)

    use_dense = capture or (S * T <= flash_threshold * flash_threshold) or cross
    if use_dense:
        qpos = positions if positions.ndim else positions[None]
        kpos = jnp.arange(T)
        mask = jnp.zeros((S, T), jnp.float32)
        if causal and not cross:
            mask = jnp.where(kpos[None, :] <= qpos[:, None], 0.0, NEG_INF)
        if window > 0 and not cross:
            mask = jnp.where((qpos[:, None] - kpos[None, :]) < window,
                             mask, NEG_INF)
        out, attn_argmax = _dense_attend(qg, kt, vt, mask, capture=capture)
    else:
        out, attn_argmax = _flash_attend(
            qg, kt, vt, causal=causal and not cross,
            window=window if not cross else 0, q_offset=positions[0])

    y = jnp.moveaxis(out, 3, 1).reshape(B, S, nh * hd).astype(x.dtype)
    y = y @ params["wo"]

    k, v = k.reshape(B, T, nkv * hd), v.reshape(B, T, nkv * hd)
    if window > 0 and not cross:
        W = min(window, T)
        shift = (T - W) % W if W else 0
        cache = {"k": jnp.roll(k[:, T - W:], shift, axis=1),
                 "v": jnp.roll(v[:, T - W:], shift, axis=1)}
    else:
        cache = {"k": k, "v": v}
    return y, cache, attn_argmax


def attention_decode_step(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (B, 1, d)
    cache: Dict[str, jnp.ndarray],  # k/v: see below
    *,
    pos,                            # absolute position: scalar or (B,) vector
    layer,                          # self-attention: block index in the stack
    causal: bool = True,
    window: int = 0,
    rope_theta: float = 0.0,
    num_heads: Optional[int] = None,
    num_kv_heads: Optional[int] = None,
    cross: bool = False,
    valid_len=None,                 # cross only: scalar or (B,) valid K/V len
    capture: bool = False,
    dense_threshold: int = 4096,
    kv_len: Optional[int] = None,
    backend: str = "jnp",
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], Optional[jnp.ndarray]]:
    """One-token decode against a KV cache. Returns (y, new_cache, argmax).

    Self-attention takes the k/v caches of every block, stacked
    (num_blocks, B, T, n_kv·hd), and the traced index ``layer`` of this
    block: it writes each row's new K/V at ``[layer, row, slot]`` in place
    (the decode scan carries the stacks) and reads back only this layer's
    rows it scores, so no layer's cache is copied out at ``T`` and written
    back. Cross-attention (``cross``) reads this layer's static encoder
    K/V, (B, S, n_kv·hd), ``valid_len`` masking encoder padding, and writes
    nothing. The rows read are viewed as (B, T', n_kv, hd).

    ``pos`` may be a scalar (whole batch at one position — the training /
    consistency-test path) or a (B,) vector of per-row positions (the
    continuous-batching serving path, where every slot decodes at its own
    offset). ``dense_threshold``: cache lengths up to this use the dense
    einsum path. Raising it past the cache length switches long-context
    decode to the dense formulation, whose softmax GSPMD can keep
    partitioned over a sequence-sharded cache (small all-reduces instead of
    an all-gather of the cache) — see EXPERIMENTS.md §Perf (gemma3
    long_500k iteration).

    ``kv_len`` is a STATIC ragged-decode hint from the serving engine:
    every row's validity (``pos + 1``) is promised to be <= ``kv_len``
    this step, so the attention read takes the layer's first ``kv_len``
    slots instead of scoring all ``max_len`` padded positions (the write
    still targets the full buffer). Ignored for windowed layers (their
    rolling cache wraps, so high slot indices stay live) and
    cross-attention.

    ``backend`` selects the attention realization: ``"jnp"`` (dense
    einsum under ``dense_threshold``, blocked flash above) or
    ``"pallas"`` — ``repro.kernels.decode_attention`` with per-row
    ``valid_len`` (compiled on a TPU, interpreted elsewhere). The kernel
    emits no score matrix, so it refuses ``capture``.

    The cache update runs under the name scope ``kv_write``.

    Windowed layers use a rolling cache of ``window`` slots (write at
    ``pos % window``); full layers write at ``pos``.

    ``capture`` (dense path only) returns the per-row argmax key position
    summed over heads — the paper's attention-ID feature — else None.
    """
    nh = num_heads or cfg.num_heads
    nkv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    T = cache["k"].shape[-2]
    g = nh // nkv
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    rope_pos = pos[:, None] if per_slot else pos[None]

    q = (x @ params["wq"]).reshape(B, 1, nh, hd)
    if "q_norm" in params:
        q = apply_norm("rmsnorm", params["q_norm"], q)
    if rope_theta > 0:
        inv = rope_frequencies(hd, rope_theta)
        q = apply_rope(q, rope_pos, inv)

    if cross:
        k_att, v_att, T_att = cache["k"], cache["v"], T
        valid = T if valid_len is None else valid_len
        new_cache = cache
    else:
        knew = (x @ params["wk"]).reshape(B, 1, nkv, hd)
        vnew = (x @ params["wv"]).reshape(B, 1, nkv, hd)
        if "k_norm" in params:
            knew = apply_norm("rmsnorm", params["k_norm"], knew)
        if rope_theta > 0:
            knew = apply_rope(knew, rope_pos, inv)
        dt = cache["k"].dtype
        knew = knew.reshape(B, nkv * hd).astype(dt)
        vnew = vnew.reshape(B, nkv * hd).astype(dt)
        slot = pos % T if window > 0 else pos
        with jax.named_scope("kv_write"):
            if per_slot:
                rows = jnp.arange(B)
                k = cache["k"].at[layer, rows, slot].set(knew, mode="drop")
                v = cache["v"].at[layer, rows, slot].set(vnew, mode="drop")
            else:
                at = (layer, 0, slot, 0)
                k = jax.lax.dynamic_update_slice(cache["k"],
                                                 knew[None, :, None], at)
                v = jax.lax.dynamic_update_slice(cache["v"],
                                                 vnew[None, :, None], at)
        valid = jnp.minimum(pos + 1, T) if window > 0 else pos + 1
        new_cache = {"k": k, "v": v}
        # ragged-decode hint: read only the slots that can be valid
        T_att = kv_len if (kv_len is not None and window == 0
                           and kv_len < T) else T
        rows_read = (1, B, T_att, nkv * hd)
        k_att = jax.lax.dynamic_slice(k, (layer, 0, 0, 0), rows_read)[0]
        v_att = jax.lax.dynamic_slice(v, (layer, 0, 0, 0), rows_read)[0]
    k_att = k_att.reshape(B, T_att, nkv, hd)
    v_att = v_att.reshape(B, T_att, nkv, hd)

    qg = jnp.moveaxis(q.reshape(B, 1, nkv, g, hd), 1, 3)
    kt = jnp.moveaxis(k_att, 1, 2)
    vt = jnp.moveaxis(v_att, 1, 2)
    attn_argmax = None
    if backend == "pallas":
        if capture:
            raise ValueError("the flash-decode kernel emits no attention "
                             "argmax; capture needs attn_backend='jnp'")
        from repro.kernels.decode_attention.ops import decode_attention_pallas
        out = decode_attention_pallas(qg[:, :, :, 0, :], k_att, v_att,
                                      valid)[:, :, :, None, :]
    elif T_att <= dense_threshold:
        tpos = jnp.arange(T_att)
        if jnp.ndim(valid) == 1:
            mask = jnp.where(tpos[None, :] < jnp.asarray(valid)[:, None],
                             0.0, NEG_INF)          # (B, T)
            mask = mask[:, None, None, None, :]     # vs scores (B,N,G,1,T)
        else:
            mask = jnp.where(tpos[None, :] < valid, 0.0, NEG_INF)
        out, attn_argmax = _dense_attend(qg, kt, vt, mask, capture=capture)
    else:
        # flash over the cache; positions already baked into rope'd keys, so
        # masking is purely slot-validity. (No capture on this path.)
        out, _ = _flash_attend(qg, kt, vt, causal=False, window=0,
                               q_offset=jnp.asarray(0), kv_valid_len=valid)
    y = jnp.moveaxis(out, 3, 1).reshape(B, 1, nh * hd).astype(x.dtype)
    return y @ params["wo"], new_cache, attn_argmax


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *,
               window: int = 0, num_kv_heads: Optional[int] = None,
               dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    nkv = num_kv_heads or cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    T = min(window, seq_len) if window > 0 else seq_len
    shape = (batch, T, nkv * hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
