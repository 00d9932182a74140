"""Public wrapper for the GQA flash-decode kernel.

``block_t=None`` (default) defers the KV tile length to the autotuner
(:mod:`repro.kernels.autotune`): short caches get small tiles (less
padding waste), long caches get wide tiles (fewer grid steps). An
explicit ``block_t`` bypasses it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.device import interpret_kernels
from repro.kernels.autotune import resolve
from repro.kernels.decode_attention.kernel import decode_attention_kernel


@partial(jax.jit, static_argnames=("block_t", "interpret"))
def _decode_attention_jit(q, k, v, valid, *, block_t, interpret):
    T = k.shape[1]
    pad = (-T) % block_t
    # the kernel reads head-major (B, N, T, D) blocks
    k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    if pad:
        cfg = ((0, 0), (0, 0), (0, pad), (0, 0))
        k = jnp.pad(k, cfg)
        v = jnp.pad(v, cfg)
    return decode_attention_kernel(q, k, v, valid, block_t=block_t,
                                   interpret=interpret)


def decode_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            valid_len, *, block_t: int | None = None,
                            interpret: bool | None = None) -> jnp.ndarray:
    """q: (B, N, G, D); k/v: (B, T, N, D); valid_len scalar or (B,)."""
    B, N, G, D = q.shape
    T = k.shape[1]
    valid = jnp.asarray(valid_len, jnp.int32)
    if valid.ndim == 0:
        valid = jnp.full((B,), valid, jnp.int32)
    if block_t is None:
        block_t = resolve("decode_attention", k.dtype,
                          B=B, H=N, G=G, D=D, T=T)["block_t"]
    bt = min(block_t, T)
    return _decode_attention_jit(q, k, v, valid, block_t=bt,
                                 interpret=interpret_kernels(interpret))
