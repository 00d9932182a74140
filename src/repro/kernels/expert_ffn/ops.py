"""Public wrapper for the expert FFN kernel.

``interpret=None`` (the default) compiles the kernel on a TPU and runs it
in the Pallas interpreter elsewhere (:func:`repro.device.interpret_kernels`).
``block_c=None`` / ``block_f=None`` defer the tile sizes to the
autotuner (:mod:`repro.kernels.autotune`); explicit values bypass it.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.device import interpret_kernels
from repro.kernels.autotune import resolve
from repro.kernels.expert_ffn.kernel import expert_ffn_kernel


def aligned_block(block: int, dim: int, sublane: int = 8) -> int:
    """Clamp a requested block size to ``dim`` and round it UP to the
    sublane multiple.

    The old clamp ``min(block, max(dim, 8))`` produced misaligned blocks
    whenever ``8 < dim < block`` (e.g. C=12 -> block 12) or the caller
    asked for a sub-sublane block — fine under ``interpret=True`` but a
    Mosaic tiling violation on a real TPU. Rounding the clamped block up
    (the data is zero-padded to match) keeps numerics identical while
    staying (8, 128)-tileable for any capacity, including ``C < 8``.
    """
    b = max(1, min(block, dim))
    return ((b + sublane - 1) // sublane) * sublane


@partial(jax.jit, static_argnames=("activation", "block_c", "block_f",
                                   "interpret"))
def _expert_ffn_jit(buf: jnp.ndarray, w_gate: jnp.ndarray,
                    w_up: Optional[jnp.ndarray], w_down: jnp.ndarray, *,
                    activation: str, block_c: int, block_f: int,
                    interpret: bool) -> jnp.ndarray:
    # pad capacity / ffn dims up to the (sublane-aligned) block multiples
    E, C, D = buf.shape
    F = w_gate.shape[-1]
    bc, bf = aligned_block(block_c, C), aligned_block(block_f, F)
    pc, pf = (-C) % bc, (-F) % bf
    if pc:
        buf = jnp.pad(buf, ((0, 0), (0, pc), (0, 0)))
    if pf:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pf)))
        if w_up is not None:
            w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        w_down = jnp.pad(w_down, ((0, 0), (0, pf), (0, 0)))
    out = expert_ffn_kernel(buf, w_gate, w_up, w_down,
                            activation=activation, block_c=bc, block_f=bf,
                            interpret=interpret)
    return out[:, :C] if pc else out


def expert_ffn_pallas(buf: jnp.ndarray, w_gate: jnp.ndarray,
                      w_up: Optional[jnp.ndarray], w_down: jnp.ndarray, *,
                      activation: str = "swiglu",
                      block_c: int | None = None,
                      block_f: int | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    E, C, D = buf.shape
    F = w_gate.shape[-1]
    if block_c is None or block_f is None:
        knobs = resolve("expert_ffn", buf.dtype, E=E, C=C, D=D, F=F)
        block_c = block_c if block_c is not None else knobs["block_c"]
        block_f = block_f if block_f is not None else knobs["block_f"]
    return _expert_ffn_jit(buf, w_gate, w_up, w_down, activation=activation,
                           block_c=block_c, block_f=block_f,
                           interpret=interpret_kernels(interpret))


def moe_expert_ffn_adapter(params, buf, activation):
    """Drop-in for ``repro.models.moe.expert_ffn`` (same signature)."""
    if activation == "swiglu":
        return expert_ffn_pallas(buf, params["w_gate"], params["w_up"],
                                 params["w_down"], activation="swiglu")
    return expert_ffn_pallas(buf, params["w_in"], None, params["w_out"],
                             activation="gelu")
