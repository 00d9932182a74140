"""Dropless ragged grouped expert FFN — Pallas TPU kernel.

Input layout (produced by ``repro.models.moe.build_grouped_dispatch``):
tokens are SORTED by expert id into a flat ``(R, D)`` buffer where each
expert's group is padded up to a multiple of ``block_rows`` (zero rows),
so every row-tile of ``block_rows`` tokens belongs to exactly ONE expert.
``tile_expert`` maps row-tile -> owning expert id.

The kernel is a ragged grouped GEMM (megablocks/gmm-style, DESIGN.md §4):
the grid walks (row_tile, ffn_tile) and the *scalar-prefetched*
``tile_expert`` array drives the weight BlockSpec index maps, so each row
tile multiplies against its own expert's weights — cost is proportional
to the ROUTED tokens (rounded up to ``block_rows`` per active expert),
never to a capacity bound, and no token is ever dropped. Per expert e
over its ragged group:

    swiglu: out = (silu(x @ Wg[e]) * (x @ Wu[e])) @ Wd[e]
    gelu:   out = gelu(x @ Wg[e]) @ Wd[e]

Like ``expert_ffn``, the ffn axis is the innermost sequential grid
dimension: partial Wd products accumulate in an f32 VMEM scratch across
ffn tiles and the output tile is written once on the last tile. VMEM per
step (block_rows=128, block_f=128, bf16) is identical to the dense
kernel's ~6 MiB at D=4096; group padding rows are zero and FFN(0) == 0,
so no masking is needed inside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _grouped_ffn_kernel(eid_ref, x_ref, *refs, activation: str):
    del eid_ref  # consumed by the BlockSpec index maps, not the body
    if activation == "swiglu":
        wg_ref, wu_ref, wd_ref, out_ref, acc_scr = refs
    else:
        wg_ref, wd_ref, out_ref, acc_scr = refs
        wu_ref = None
    f = pl.program_id(1)
    nf = pl.num_programs(1)

    x = x_ref[...].astype(jnp.float32)        # (bn, D)
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
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


def grouped_moe_kernel(x_sorted: jnp.ndarray, tile_expert: jnp.ndarray,
                       w_gate: jnp.ndarray, w_up, w_down: jnp.ndarray,
                       *, activation: str = "swiglu", block_rows: int = 128,
                       block_f: int = 128,
                       interpret: bool) -> jnp.ndarray:
    R, D = x_sorted.shape
    E, _, F = w_gate.shape
    assert R % block_rows == 0 and F % block_f == 0, (R, F, block_rows,
                                                      block_f)
    nt, nf = R // block_rows, F // block_f
    assert tile_expert.shape == (nt,), (tile_expert.shape, nt)

    x_spec = pl.BlockSpec((block_rows, D), lambda i, f, eid: (i, 0))
    w_in_spec = pl.BlockSpec((1, D, block_f),
                             lambda i, f, eid: (eid[i], 0, f))
    wd_spec = pl.BlockSpec((1, block_f, D),
                           lambda i, f, eid: (eid[i], f, 0))
    out_spec = pl.BlockSpec((block_rows, D), lambda i, f, eid: (i, 0))

    if activation == "swiglu":
        assert w_up is not None
        in_specs = [x_spec, w_in_spec, w_in_spec, wd_spec]
        args = (x_sorted, w_gate, w_up, w_down)
    else:
        in_specs = [x_spec, w_in_spec, wd_spec]
        args = (x_sorted, w_gate, w_down)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, nf),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((block_rows, D), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_grouped_ffn_kernel, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, D), x_sorted.dtype),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), *args)
