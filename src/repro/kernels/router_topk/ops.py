"""Public wrappers for the fused router top-k kernel.

``block_n=None`` (the default) defers the tile height to the autotuner
(:mod:`repro.kernels.autotune`), which scores candidates against the TPU
v5e roofline (padding waste vs. per-tile launch overhead) and caches the
choice per ``(kernel, dtype, dims)``. Passing an explicit ``block_n``
bypasses the autotuner, which is what the oracle harness does to pin
padded-shape regressions.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.device import interpret_kernels
from repro.kernels.autotune import resolve
from repro.kernels.router_topk.kernel import (router_topk_fused_kernel,
                                              router_topk_kernel)


@partial(jax.jit, static_argnames=("k", "valid_experts", "block_n",
                                   "interpret"))
def _router_topk_jit(x, router_w, *, k, valid_experts, block_n, interpret):
    N = x.shape[0]
    pad = (-N) % block_n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    vals, idx = router_topk_kernel(x, router_w, k=k,
                                   valid_experts=valid_experts,
                                   block_n=block_n, valid_rows=N,
                                   interpret=interpret)
    return (vals[:N], idx[:N]) if pad else (vals, idx)


@partial(jax.jit, static_argnames=("k", "valid_experts", "block_n",
                                   "interpret"))
def _router_topk_fused_jit(x, router_w, *, k, valid_experts, block_n,
                           interpret):
    N = x.shape[0]
    pad = (-N) % block_n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    vals, idx, pos, counts, stats = router_topk_fused_kernel(
        x, router_w, k=k, valid_experts=valid_experts, block_n=block_n,
        valid_rows=N, interpret=interpret)
    return (vals[:N], idx[:N], pos[:N], counts[0], stats[0], stats[1, 0])


def router_topk_pallas(x: jnp.ndarray, router_w: jnp.ndarray, *, k: int,
                       valid_experts: int | None = None,
                       block_n: int | None = None,
                       interpret: bool | None = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Router gating: returns (normalized top-k weights, expert indices).

    Token rows are zero-padded up to a ``block_n`` multiple for the grid;
    padded rows are masked inside the kernel (inert: no prob mass, no
    expert slot) and sliced off here.
    """
    N, D = x.shape
    E = router_w.shape[-1]
    ve = valid_experts if valid_experts is not None else E
    if block_n is None:
        block_n = resolve("router_topk", x.dtype,
                          N=N, D=D, E=E, k=k)["block_n"]
    bn = min(block_n, N)
    return _router_topk_jit(x, router_w, k=k, valid_experts=ve, block_n=bn,
                            interpret=interpret_kernels(interpret))


def router_topk_fused_pallas(x: jnp.ndarray, router_w: jnp.ndarray, *,
                             k: int, valid_experts: int | None = None,
                             block_n: int | None = None,
                             interpret: bool | None = None):
    """One-pass routing + dispatch metadata.

    Returns ``(vals (N, k) f32, idx (N, k) i32, pos_in_e (N, k) i32,
    counts (E,) i32, probs_sum (E,) f32, z_sq_sum () f32)``.

    ``pos_in_e`` is each routed pair's stable within-expert rank in
    flattened (token, k) order — bit-equal to the rank
    ``repro.models.moe.build_dispatch`` derives from its stable
    argsort-by-expert, so capacity buffers and grouped ragged layouts
    built from it are bit-identical to the separate-pass plans.
    ``probs_sum`` / ``z_sq_sum`` are the router-loss sufficient
    statistics summed over the true (unpadded) rows.
    """
    N, D = x.shape
    E = router_w.shape[-1]
    ve = valid_experts if valid_experts is not None else E
    if block_n is None:
        block_n = resolve("router_topk", x.dtype,
                          N=N, D=D, E=E, k=k)["block_n"]
    bn = min(block_n, N)
    return _router_topk_fused_jit(x, router_w, k=k, valid_experts=ve,
                                  block_n=bn,
                                  interpret=interpret_kernels(interpret))
