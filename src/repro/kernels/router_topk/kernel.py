"""Fused router matmul + softmax + top-k (+ dispatch metadata) — Pallas TPU.

One grid step processes a (block_n, D) token tile: logits = x @ W in the
MXU, a numerically-stable softmax in VREGs, then k iterations of
(max, argmax-via-iota, mask) extract the top-k experts entirely on-chip —
no (N, E) probability tensor ever round-trips to HBM. E is small (<= 128)
so the whole expert axis lives in one VMEM tile.

Two entry points share the per-tile routing math:

* :func:`router_topk_kernel` — weights + indices only (the original
  gating kernel).
* :func:`router_topk_fused_kernel` — additionally emits, per routed
  (token, k) pair, its stable within-expert rank ``pos_in_e`` plus the
  per-expert pair counts and the router-loss sufficient statistics
  (sum of softmax probs per expert, sum of logsumexp^2). The grid's
  innermost axis is sequential on TPU, so running per-expert counters
  accumulate in the output block (constant index map) across tiles —
  replacing the separate ``argsort`` + ``bincount`` + ``cumsum`` HBM
  passes that ``repro.models.moe.build_dispatch`` /
  ``build_grouped_dispatch`` otherwise run.

Rows at index >= ``valid_rows`` (zero-padding added by the ops wrapper to
reach a ``block_n`` multiple) are INERT: their probs are zeroed, they can
never win a ``valid_experts`` slot, and they are excluded from the counts
and loss statistics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tile_topk(x_ref, w_ref, *, k: int, valid_experts: int,
               valid_rows: int, block_n: int):
    """Shared per-tile routing math.

    Returns (probs (bn, E) with padded rows zeroed, vals (bn, k)
    normalized, idx (bn, k) i32, live_row (bn, 1) bool, logsumexp (bn, 1)).
    """
    n = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)            # (bn, D)
    w = w_ref[...].astype(jnp.float32)            # (D, E)
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    bn, E = logits.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, E), 1)
    logits = jnp.where(col < valid_experts, logits, -1e9)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    psum = p.sum(axis=-1, keepdims=True)
    probs = p / psum
    # zero-pad rows (beyond the true N) are inert: no prob mass at all,
    # so they can never claim a capacity slot or skew the counts
    row = n * block_n + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    live_row = row < valid_rows                                 # (bn, 1)
    probs = jnp.where(live_row, probs, 0.0)
    lse = m + jnp.log(psum)                                     # (bn, 1)

    work = probs
    vals = []
    idxs = []
    for _ in range(k):
        v = work.max(axis=-1)                                   # (bn,)
        is_max = work == v[:, None]
        # first argmax via iota trick (ties -> lowest index)
        i = jnp.where(is_max, col, E).min(axis=-1)
        vals.append(v)
        idxs.append(i)
        work = jnp.where(col == i[:, None], -1.0, work)
    v_stack = jnp.stack(vals, axis=-1)                          # (bn, k)
    total = jnp.maximum(v_stack.sum(-1, keepdims=True), 1e-9)
    v_stack = v_stack / total
    i_stack = jnp.stack(idxs, axis=-1).astype(jnp.int32)
    # dead rows: zero weight, expert 0 (sliced off by the wrapper anyway)
    v_stack = jnp.where(live_row, v_stack, 0.0)
    i_stack = jnp.where(live_row, i_stack, 0)
    return probs, v_stack, i_stack, live_row, lse


def _router_kernel(x_ref, w_ref, vals_ref, idx_ref, *, k: int,
                   valid_experts: int, valid_rows: int, block_n: int):
    _, vals, idx, _, _ = _tile_topk(
        x_ref, w_ref, k=k, valid_experts=valid_experts,
        valid_rows=valid_rows, block_n=block_n)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx


def _router_fused_kernel(x_ref, w_ref, vals_ref, idx_ref, pos_ref,
                         counts_ref, stats_ref, *, k: int,
                         valid_experts: int, valid_rows: int, block_n: int):
    n = pl.program_id(0)
    probs, vals, idx, live_row, lse = _tile_topk(
        x_ref, w_ref, k=k, valid_experts=valid_experts,
        valid_rows=valid_rows, block_n=block_n)
    bn, E = probs.shape
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idx

    # counts/stats blocks have a constant index map: they stay resident
    # across the sequential grid, acting as running accumulators
    @pl.when(n == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        stats_ref[...] = jnp.zeros_like(stats_ref)

    # stable within-expert rank in flattened row-major (token, k) order —
    # bit-equal to the rank a stable argsort-by-expert assigns in
    # build_dispatch. A token's k experts are distinct, so a pair's rank
    # is the number of earlier live tokens routed to its expert: an
    # exclusive prefix sum over the tile's tokens, taken as a strictly
    # lower-triangular matmul (Mosaic has no cumsum). Operands are 0/1
    # and accumulation is f32, so the counts are exact.
    colE = jax.lax.broadcasted_iota(jnp.int32, (bn, E), 1)
    hits = [jnp.where((colE == idx[:, j:j + 1]) & live_row, 1.0, 0.0)
            for j in range(k)]                                  # k x (bn, E)
    routed = functools.reduce(jnp.add, hits)                    # (bn, E) 0/1
    row_i = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    earlier = jnp.where(col_i < row_i, 1.0, 0.0)
    excl = jnp.dot(earlier, routed, preferred_element_type=jnp.float32)
    base = counts_ref[0, :]                                     # (E,)
    rank = excl.astype(jnp.int32) + base[None, :]
    pos_ref[...] = jnp.stack(
        [(rank * h.astype(jnp.int32)).sum(-1) for h in hits], axis=-1)
    counts_ref[0, :] = base + routed.sum(0).astype(jnp.int32)

    # router-loss sufficient statistics: per-expert prob mass and
    # sum(logsumexp^2) over live rows (z broadcast across the row so the
    # wrapper can read element [1, 0])
    z_blk = jnp.sum(jnp.where(live_row, lse * lse, 0.0))
    stats_ref[0, :] = stats_ref[0, :] + probs.sum(0)
    stats_ref[1, :] = stats_ref[1, :] + z_blk


def router_topk_kernel(x: jnp.ndarray, router_w: jnp.ndarray, *, k: int,
                       valid_experts: int, block_n: int = 256,
                       valid_rows: int | None = None,
                       interpret: bool):
    N, D = x.shape
    E = router_w.shape[-1]
    block_n = min(block_n, N)
    assert N % block_n == 0
    vr = N if valid_rows is None else valid_rows
    grid = (N // block_n,)
    return pl.pallas_call(
        functools.partial(_router_kernel, k=k, valid_experts=valid_experts,
                          valid_rows=vr, block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, D), lambda n: (n, 0)),
                  pl.BlockSpec((D, E), lambda n: (0, 0))],
        out_specs=[pl.BlockSpec((block_n, k), lambda n: (n, 0)),
                   pl.BlockSpec((block_n, k), lambda n: (n, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, k), jnp.float32),
                   jax.ShapeDtypeStruct((N, k), jnp.int32)],
        interpret=interpret,
    )(x, router_w)


def router_topk_fused_kernel(x: jnp.ndarray, router_w: jnp.ndarray, *,
                             k: int, valid_experts: int, block_n: int = 256,
                             valid_rows: int | None = None,
                             interpret: bool):
    """Routing + dispatch metadata in one pass.

    Returns ``(vals (N, k) f32, idx (N, k) i32, pos_in_e (N, k) i32,
    counts (1, E) i32, stats (2, E) f32)`` where ``stats[0]`` is the
    per-expert softmax prob mass summed over live rows and ``stats[1, 0]``
    is ``sum(logsumexp(logits)^2)`` over live rows.
    """
    N, D = x.shape
    E = router_w.shape[-1]
    block_n = min(block_n, N)
    assert N % block_n == 0
    vr = N if valid_rows is None else valid_rows
    grid = (N // block_n,)
    return pl.pallas_call(
        functools.partial(_router_fused_kernel, k=k,
                          valid_experts=valid_experts, valid_rows=vr,
                          block_n=block_n),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, D), lambda n: (n, 0)),
                  pl.BlockSpec((D, E), lambda n: (0, 0))],
        out_specs=[pl.BlockSpec((block_n, k), lambda n: (n, 0)),
                   pl.BlockSpec((block_n, k), lambda n: (n, 0)),
                   pl.BlockSpec((block_n, k), lambda n: (n, 0)),
                   pl.BlockSpec((1, E), lambda n: (0, 0)),
                   pl.BlockSpec((2, E), lambda n: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((N, k), jnp.float32),
                   jax.ShapeDtypeStruct((N, k), jnp.int32),
                   jax.ShapeDtypeStruct((N, k), jnp.int32),
                   jax.ShapeDtypeStruct((1, E), jnp.int32),
                   jax.ShapeDtypeStruct((2, E), jnp.float32)],
        interpret=interpret,
    )(x, router_w)
