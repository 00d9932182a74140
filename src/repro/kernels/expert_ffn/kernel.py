"""Blocked grouped expert FFN — Pallas TPU kernel.

Computes, per expert e over its (C, D) capacity buffer:

    swiglu: out = (silu(x @ Wg) * (x @ Wu)) @ Wd
    gelu:   out = gelu(x @ Wg) @ Wd

TPU adaptation (DESIGN.md §4): instead of the GPU megablocks-style ragged
GMM, the dispatch layer produces dense per-expert capacity buffers (invalid
slots are zero, and FFN(0) == 0 with no biases, so no masking is needed).
The grid tiles (expert, capacity, ffn): the ffn axis is the innermost,
sequential dimension so partial Wd products accumulate in an f32 VMEM
scratch across ffn tiles; the output block is written once on the last
tile (single HBM store, full f32 accuracy even for bf16 I/O).

VMEM working set per grid step (defaults block_c=128, block_f=128, bf16):
x 128xD(2B) + Wg,Wu Dx128(2B each) + Wd 128xD(2B) + acc 128xD(4B)
= 12 * 128 * D bytes ~= 6 MiB at D=4096 — inside the ~16 MiB VMEM budget,
MXU-aligned (128-multiples).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ffn_kernel(x_ref, *refs, activation: str):
    if activation == "swiglu":
        wg_ref, wu_ref, wd_ref, out_ref, acc_scr = refs
    else:
        wg_ref, wd_ref, out_ref, acc_scr = refs
        wu_ref = None
    f = pl.program_id(2)
    nf = pl.num_programs(2)

    x = x_ref[0].astype(jnp.float32)          # (bc, D)
    wg = wg_ref[0].astype(jnp.float32)        # (D, bf)
    wd = wd_ref[0].astype(jnp.float32)        # (bf, D)
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    if wu_ref is not None:
        u = jnp.dot(x, wu_ref[0].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(g)
    partial = jnp.dot(h, wd, preferred_element_type=jnp.float32)

    @pl.when(f == 0)
    def _init():
        acc_scr[...] = partial

    @pl.when(f > 0)
    def _acc():
        acc_scr[...] = acc_scr[...] + partial

    @pl.when(f == nf - 1)
    def _emit():
        out_ref[0] = acc_scr[...].astype(out_ref.dtype)


def expert_ffn_kernel(buf: jnp.ndarray, w_gate: jnp.ndarray,
                      w_up: Optional[jnp.ndarray], w_down: jnp.ndarray,
                      *, activation: str = "swiglu", block_c: int = 128,
                      block_f: int = 128,
                      interpret: bool) -> jnp.ndarray:
    E, C, D = buf.shape
    F = w_gate.shape[-1]
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    assert C % block_c == 0 and F % block_f == 0, (C, F, block_c, block_f)
    nc, nf = C // block_c, F // block_f
    grid = (E, nc, nf)

    x_spec = pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0))
    w_in_spec = pl.BlockSpec((1, D, block_f), lambda e, c, f: (e, 0, f))
    wd_spec = pl.BlockSpec((1, block_f, D), lambda e, c, f: (e, f, 0))
    out_spec = pl.BlockSpec((1, block_c, D), lambda e, c, f: (e, c, 0))

    if activation == "swiglu":
        assert w_up is not None
        in_specs = [x_spec, w_in_spec, w_in_spec, wd_spec]
        args = (buf, w_gate, w_up, w_down)
    else:
        in_specs = [x_spec, w_in_spec, wd_spec]
        args = (buf, w_gate, w_down)

    return pl.pallas_call(
        functools.partial(_ffn_kernel, activation=activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((E, C, D), buf.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, D), jnp.float32)],
        interpret=interpret,
    )(*args)
