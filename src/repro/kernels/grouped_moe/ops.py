"""Public jit'd wrapper for the dropless ragged grouped-GEMM MoE kernel.

``interpret=None`` (the default) compiles the kernel on a TPU and runs it
in the Pallas interpreter elsewhere (:func:`repro.device.interpret_kernels`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.device import interpret_kernels
from repro.kernels.autotune import resolve
from repro.kernels.expert_ffn.ops import aligned_block
from repro.kernels.grouped_moe.kernel import grouped_moe_kernel


@partial(jax.jit, static_argnames=("activation", "block_f", "interpret"))
def _grouped_moe_jit(x_sorted: jnp.ndarray, tile_expert: jnp.ndarray,
                     w_gate: jnp.ndarray, w_up, w_down: jnp.ndarray, *,
                     activation: str, block_f: int,
                     interpret: bool) -> jnp.ndarray:
    R, D = x_sorted.shape
    nt = tile_expert.shape[0]
    assert R % nt == 0, (R, nt)
    block_rows = R // nt
    F = w_gate.shape[-1]
    bf = aligned_block(block_f, F)   # sublane-aligned, F zero-padded below
    pf = (-F) % bf
    if pf:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pf)))
        if w_up is not None:
            w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        w_down = jnp.pad(w_down, ((0, 0), (0, pf), (0, 0)))
    return grouped_moe_kernel(x_sorted, tile_expert, w_gate, w_up, w_down,
                              activation=activation, block_rows=block_rows,
                              block_f=bf, interpret=interpret)


def grouped_moe_pallas(x_sorted: jnp.ndarray, tile_expert: jnp.ndarray,
                       w_gate: jnp.ndarray, w_up, w_down: jnp.ndarray, *,
                       activation: str = "swiglu",
                       block_f: int | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """x_sorted: (R, D) expert-sorted token rows, each ``R // len(tile_expert)``
    row tile owned by expert ``tile_expert[t]`` (group padding rows are
    zero). Returns the per-row expert FFN output, same shape/dtype.
    ``block_f=None`` defers the FFN tile width to the autotuner."""
    R, D = x_sorted.shape
    F = w_gate.shape[-1]
    if block_f is None:
        block_f = resolve("grouped_moe", x_sorted.dtype,
                          rows=R, D=D, F=F)["block_f"]
    return _grouped_moe_jit(x_sorted, tile_expert, w_gate, w_up, w_down,
                            activation=activation, block_f=block_f,
                            interpret=interpret_kernels(interpret))


def moe_grouped_ffn_adapter(params, x_sorted, tile_expert, activation):
    """Drop-in for ``repro.models.moe.grouped_expert_ffn`` (same signature)."""
    if activation == "swiglu":
        return grouped_moe_pallas(x_sorted, tile_expert, params["w_gate"],
                                  params["w_up"], params["w_down"],
                                  activation="swiglu")
    return grouped_moe_pallas(x_sorted, tile_expert, params["w_in"], None,
                              params["w_out"], activation="gelu")
