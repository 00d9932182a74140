"""Expert-parallel MoE layer via shard_map + all_to_all — the TPU-native
realization of the paper's scatter-gather communication designs
(DESIGN.md §4).

Mapping:
* scatter (gating -> experts)   = all_to_all of capacity-buffer chunks over
                                  the ``model`` axis (experts live there);
* gather (experts -> non-MoE)   = the reverse all_to_all + weighted combine;
* a=3 "direct transfer"         = ``beta=1``: one monolithic all_to_all;
* a=1 "pipelined indirect, degree beta" = the capacity axis split into
  ``beta`` chunks processed in a lax.scan — each chunk's return all_to_all
  can overlap the next chunk's expert FFN under XLA's async collectives
  (collective-start/done), which is the TPU analogue of overlapping the
  S3 upload of minibatch t-1 with the download+compute of minibatch t;
* the payload cap D^p           = a ceiling on the per-chunk all_to_all
  message size (``max_chunk_bytes``).

Layout inside shard_map (DeepSpeed-MoE style): tokens are split over
``model`` ranks within each data shard for routing, so the all_to_all
exchanges (model_size, E_local, C_chunk, d) blocks; expert FFN runs on
(E_local, model_size * C_chunk, d) — optionally via the Pallas kernel.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, MoEConfig
from repro.dispatch.chunks import chunk_count
from repro.models.common import Params
from repro.models.mlp import mlp_forward
from repro.models.moe import (build_dispatch, build_grouped_dispatch,
                              capacity_for, combine_grouped, combine_tokens,
                              dispatch_grouped, dispatch_tokens, expert_ffn,
                              grouped_expert_ffn, local_experts, route)


# β-chunk sizing now lives in the transport-agnostic dispatch substrate
# (repro.dispatch.chunks) so the shard_map loops here and the process
# gateway size their chunks identically; the old private name stays as
# an alias for downstream callers.
_chunk_count = chunk_count


def expert_parallel_moe(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (B, S, d) — sharded P(data, None, None)
    mesh: Mesh,
    *,
    beta: int = 1,
    max_chunk_bytes: Optional[int] = None,
    use_kernel: bool = False,
    data_axis: str = "data",
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full MoE layer with explicit expert parallelism.

    Router weights are replicated; expert weights are sharded over
    ``model_axis`` (E_pad divides the axis). Returns (y, aux) like
    ``repro.models.moe.moe_forward``.
    """
    m = cfg.moe
    assert m is not None
    msize = mesh.shape[model_axis]
    E_pad = params["router"].shape[-1]
    assert E_pad % msize == 0, (E_pad, msize)
    e_local = E_pad // msize
    B, S, d = x.shape

    def local_moe(router_w, w_gate, w_up, w_down, shared_p, x_blk):
        # x_blk: (B_loc, S, d) per data shard, replicated over model ranks.
        n_tot = x_blk.shape[0] * x_blk.shape[1]
        xf = x_blk.reshape(n_tot, d)
        ridx = jax.lax.axis_index(model_axis)
        n_loc = n_tot // msize
        x_loc = jax.lax.dynamic_slice_in_dim(xf, ridx * n_loc, n_loc)

        r = route(router_w, x_loc, m, valid_experts=m.num_experts)
        C = capacity_for(n_loc, m, E_pad, multiple=max(msize, 8))
        plan = build_dispatch(r.topk_idx, E_pad, C)
        buf = dispatch_tokens(x_loc, plan, E_pad)        # (E_pad, C, d)

        nb = _chunk_count(C, d, beta, max_chunk_bytes, msize, e_local,
                          jnp.dtype(x_blk.dtype).itemsize)
        Cc = C // nb
        # (nb, E_pad, Cc, d) -> scan over chunks
        chunks = jnp.moveaxis(
            buf.reshape(E_pad, nb, Cc, d), 1, 0)

        def chunk_body(_, chunk):
            # scatter: all_to_all over the model axis (experts -> owners)
            blk = chunk.reshape(msize, e_local, Cc, d)
            recv = jax.lax.all_to_all(blk, model_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # recv: (msize, e_local, Cc, d) — token slices from every rank
            eb = jnp.moveaxis(recv, 0, 1).reshape(e_local, msize * Cc, d)
            if use_kernel:
                from repro.kernels.expert_ffn.ops import moe_expert_ffn_adapter
                local_params = {
                    k: v for k, v in (("w_gate", w_gate), ("w_up", w_up),
                                      ("w_down", w_down)) if v is not None}
                if cfg.activation != "swiglu":
                    local_params = {"w_in": w_gate, "w_out": w_down}
                out = moe_expert_ffn_adapter(local_params, eb,
                                             cfg.activation)
            else:
                p_loc = ({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
                         if cfg.activation == "swiglu"
                         else {"w_in": w_gate, "w_out": w_down})
                out = expert_ffn(p_loc, eb, cfg.activation)
            # gather: reverse all_to_all (owners -> original ranks)
            out = jnp.moveaxis(out.reshape(e_local, msize, Cc, d), 1, 0)
            back = jax.lax.all_to_all(out, model_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            return None, back.reshape(E_pad, Cc, d)

        _, outs = jax.lax.scan(chunk_body, None, chunks)
        buf_out = jnp.moveaxis(outs, 0, 1).reshape(E_pad, C, d)
        y_loc = combine_tokens(buf_out, plan, r.topk_weight)
        if m.num_shared_experts > 0:
            y_loc = y_loc + mlp_forward(shared_p, x_loc, cfg.activation)
        # reassemble the data shard's tokens from all model ranks
        y = jax.lax.all_gather(y_loc, model_axis, axis=0, tiled=True)
        # aux leaves are emitted replicated (out_spec P()): reduce over
        # every mesh axis
        all_axes = tuple(mesh.axis_names)
        aux = {
            "lb_loss": jax.lax.pmean(r.lb_loss, all_axes) * m.router_aux_coef,
            "z_loss": jax.lax.pmean(r.z_loss, all_axes) * m.router_z_coef,
            "expert_counts": jax.lax.psum(plan.expert_counts, all_axes),
        }
        return y.reshape(x_blk.shape).astype(x_blk.dtype), aux

    axes = tuple(a for a in ("pod", data_axis) if a in mesh.axis_names)
    bspec = axes if len(axes) > 1 else axes[0]
    wg = params.get("w_gate", params.get("w_in"))
    wu = params.get("w_up")
    wd = params.get("w_down", params.get("w_out"))
    shared_p = params.get("shared", {})
    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(), P(model_axis, None, None),
                  P(model_axis, None, None) if wu is not None else P(),
                  P(model_axis, None, None), P(),
                  P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()), check_vma=False)
    return fn(params["router"], wg,
              wu if wu is not None else jnp.zeros(()), wd, shared_p, x)


def expert_parallel_moe_grouped(
    params: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (B, S, d) — sharded P(data, None, None)
    mesh: Mesh,
    *,
    beta: int = 1,
    use_kernel: bool = False,
    block_rows: int = 8,
    data_axis: str = "data",
    model_axis: str = "model",
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """DROPLESS expert-parallel MoE: gather-based ragged grouped GEMM.

    Where :func:`expert_parallel_moe` all_to_alls fixed-capacity buffers
    (dropping overflow exactly like the local dense path), this variant
    sorts each rank's tokens by expert into block-aligned ragged groups
    (``repro.models.moe.build_grouped_dispatch``) and pipelines the
    sorted row axis in ``beta`` chunks — the paper's flexibly pipelined
    scatter-gather with the β-chunk schedule applied to SORTED expert
    groups, so a chunk's payload is proportional to the tokens actually
    routed, never to a capacity bound. Per chunk:

    * scatter: ``all_gather`` of the chunk's sorted rows + tile->expert
      map over the ``model`` axis (every rank sees every rank's groups);
    * compute: each rank runs the grouped FFN (jnp blocked fast path or
      the ``grouped_moe`` Pallas kernel) over the gathered tiles and
      MASKS the output of tiles whose expert it does not own. Tile
      ownership is data-dependent, so under XLA's static shapes each
      rank's GEMM grid spans all gathered tiles — the ragged layout
      shrinks the COMM payload and the global row count with realized
      load, while per-rank FLOPs stay gather-sized (a TPU kernel would
      predicate the foreign tiles out of the grid via the prefetched
      tile map);
    * gather: ``psum_scatter`` returns each rank its own rows, summed
      across owners (each tile has exactly one owner, so the sum is
      exact).

    Under XLA's async collectives each chunk's return psum_scatter can
    overlap the next chunk's expert FFN, mirroring the a=1 design.
    ``beta`` follows the plan's per-layer ``chunk_schedule`` via
    ``repro.launch.specs.ep_config_for_plan(..., executor="grouped")``.
    Returns (y, aux) like ``moe_forward``; aux["expert_counts"] is the
    global pre-drop histogram (== kept: nothing is dropped).
    """
    m = cfg.moe
    assert m is not None
    msize = mesh.shape[model_axis]
    E_pad = params["router"].shape[-1]
    assert E_pad % msize == 0, (E_pad, msize)
    e_local = E_pad // msize
    B, S, d = x.shape

    def local_moe(router_w, w_gate, w_up, w_down, shared_p, x_blk):
        n_tot = x_blk.shape[0] * x_blk.shape[1]
        xf = x_blk.reshape(n_tot, d)
        ridx = jax.lax.axis_index(model_axis)
        n_loc = n_tot // msize
        x_loc = jax.lax.dynamic_slice_in_dim(xf, ridx * n_loc, n_loc)

        r = route(router_w, x_loc, m, valid_experts=m.num_experts)
        nb = max(1, min(beta, n_loc))
        gd = build_grouped_dispatch(r.topk_idx, E_pad,
                                    block_rows=block_rows, row_multiple=nb)
        buf = dispatch_grouped(x_loc, gd)                # (R, d) sorted rows
        R = gd.num_rows
        rows_c = R // nb
        tiles_c = rows_c // block_rows
        chunks_x = buf.reshape(nb, rows_c, d)
        chunks_t = gd.tile_expert.reshape(nb, tiles_c)

        if cfg.activation == "swiglu":
            p_loc = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        else:
            p_loc = {"w_in": w_gate, "w_out": w_down}

        def chunk_body(_, ch):
            xc, tc = ch
            # scatter: every rank sees every rank's sorted chunk + groups
            gx = jax.lax.all_gather(xc, model_axis, axis=0)  # (msize,rows,d)
            gt = jax.lax.all_gather(tc, model_axis, axis=0)  # (msize,tiles)
            local, owned = local_experts(gt.reshape(-1), ridx * e_local,
                                         e_local)
            lidx = jnp.clip(local, 0, e_local - 1)
            rows = gx.reshape(msize * rows_c, d)
            if use_kernel:
                from repro.kernels.grouped_moe.ops import (
                    moe_grouped_ffn_adapter)
                out = moe_grouped_ffn_adapter(p_loc, rows, lidx,
                                              cfg.activation)
            else:
                out = grouped_expert_ffn(p_loc, rows, lidx, cfg.activation)
            # mask tiles owned by other ranks: exactly one rank computes
            # each tile, so the cross-rank sum below is exact
            out = (out.reshape(msize * tiles_c, block_rows, d)
                   * owned[:, None, None].astype(out.dtype))
            # gather: each rank receives its own rows, summed over owners
            back = jax.lax.psum_scatter(
                out.reshape(msize * rows_c, d), model_axis,
                scatter_dimension=0, tiled=True)
            return None, back

        _, outs = jax.lax.scan(chunk_body, None, (chunks_x, chunks_t))
        buf_out = outs.reshape(R, d)
        y_loc = combine_grouped(buf_out, gd, r.topk_weight)
        if m.num_shared_experts > 0:
            y_loc = y_loc + mlp_forward(shared_p, x_loc, cfg.activation)
        y = jax.lax.all_gather(y_loc, model_axis, axis=0, tiled=True)
        all_axes = tuple(mesh.axis_names)
        aux = {
            "lb_loss": jax.lax.pmean(r.lb_loss, all_axes) * m.router_aux_coef,
            "z_loss": jax.lax.pmean(r.z_loss, all_axes) * m.router_z_coef,
            "expert_counts": jax.lax.psum(gd.expert_counts, all_axes),
        }
        return y.reshape(x_blk.shape).astype(x_blk.dtype), aux

    axes = tuple(a for a in ("pod", data_axis) if a in mesh.axis_names)
    bspec = axes if len(axes) > 1 else axes[0]
    wg = params.get("w_gate", params.get("w_in"))
    wu = params.get("w_up")
    wd = params.get("w_down", params.get("w_out"))
    shared_p = params.get("shared", {})
    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(), P(model_axis, None, None),
                  P(model_axis, None, None) if wu is not None else P(),
                  P(model_axis, None, None), P(),
                  P(bspec, None, None)),
        out_specs=(P(bspec, None, None), P()), check_vma=False)
    return fn(params["router"], wg,
              wu if wu is not None else jnp.zeros(()), wd, shared_p, x)
