"""Mixture-of-Experts layer: top-k router + pluggable dispatch executors.

One routing front-end (:func:`route`) feeds three interchangeable
executors, selected by ``moe_forward(..., executor=...)``:

* ``"dense"``   -- GShard-style sort-based capacity buffers: token/expert
  pairs are sorted by expert, assigned a position inside their expert's
  fixed-capacity ``(E, C, d)`` buffer, processed by a batched expert FFN,
  and combined back with the router weights. Overflowing tokens are
  DROPPED (capacity factor controls the drop rate) — the mechanism the
  paper's deployment policy sizes memory for.
* ``"grouped"`` -- dropless ragged grouped GEMM: pairs are sorted by
  expert into block-aligned ragged groups (no capacity bound, no drops);
  compute cost is proportional to the tokens actually routed, not to a
  padded capacity. The Pallas realization lives in
  ``repro.kernels.grouped_moe``; the jnp fast path here uses the same
  layout with a blocked per-tile einsum.
* ``"oracle"``  -- every expert computed for every token, top-k mixed
  (O(N*E*ff), tests/benchmarks only).

The dense and grouped executors run their stages under the name scopes
``router``, ``dispatch``, ``experts`` (shared experts too) and
``combine`` (``jax.named_scope``), which the profiler's trace carries in
each op's ``op_name``.

Every executor emits a shared :class:`RoutingSummary` (per-expert routed
/kept/dropped counts, drop mask, group offsets) consumed by the serving
telemetry, so downstream cost measurements see exactly what the execution
path computed or refused to compute.

The same dispatch plans also feed the distributed layer
(``repro.distributed.moe_parallel``: all_to_all capacity buffers, or the
gather-based dropless grouped variant) and the Pallas kernels
(``repro.kernels.expert_ffn`` on capacity buffers,
``repro.kernels.grouped_moe`` on sorted ragged groups).
"""
from __future__ import annotations

import math
from fractions import Fraction
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig, ModelConfig
from repro.models.common import Params, dense_init, split_keys
from repro.models.mlp import init_mlp, mlp_forward

MOE_EXECUTORS = ("dense", "grouped", "oracle")

# how the routing front-end is computed (all three feed the same
# executors through the same dispatch layouts):
#   "fused"     -- single-pass jnp twin of the fused Pallas kernel: one
#                  top_k plus a one-hot cumsum yields the within-expert
#                  ranks and counts directly; no argsort, no second
#                  bincount/cumsum pass. Integer outputs are bit-equal
#                  to "reference".
#   "reference" -- the original separate passes (top_k, then
#                  argsort+bincount+cumsum inside build_dispatch /
#                  build_grouped_dispatch). Kept as the differential
#                  oracle.
#   "pallas"    -- repro.kernels.router_topk.router_topk_fused_pallas:
#                  the matmul+softmax+top-k+rank+counts kernel
#                  (compiled on a TPU, interpreted elsewhere;
#                  tolerance-pinned, integers exact).
ROUTER_IMPLS = ("fused", "reference", "pallas")


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_moe(key: jax.Array, cfg: ModelConfig, *,
             num_experts: Optional[int] = None) -> Params:
    m = cfg.moe
    assert m is not None
    E = num_experts or m.num_experts
    d, ff = cfg.d_model, m.d_expert_ff
    ks = split_keys(key, 5)
    p: Params = {"router": dense_init(ks[0], (d, E))}
    if cfg.activation == "swiglu":
        p["w_gate"] = dense_init(ks[1], (E, d, ff))
        p["w_up"] = dense_init(ks[2], (E, d, ff))
        p["w_down"] = dense_init(ks[3], (E, ff, d))
    else:
        p["w_in"] = dense_init(ks[1], (E, d, ff))
        p["w_out"] = dense_init(ks[2], (E, ff, d))
    if m.num_shared_experts > 0:
        p["shared"] = init_mlp(ks[4], d, m.num_shared_experts * m.shared_ff,
                               cfg.activation)
    return p


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

class RouterOut(NamedTuple):
    topk_idx: jnp.ndarray      # (N, k) int32
    topk_weight: jnp.ndarray   # (N, k) f32, normalized
    probs: jnp.ndarray         # (N, E) f32
    lb_loss: jnp.ndarray       # scalar
    z_loss: jnp.ndarray        # scalar


def route(router_w: jnp.ndarray, x_flat: jnp.ndarray,
          m: MoEConfig, valid_experts: Optional[int] = None) -> RouterOut:
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    E_total = logits.shape[-1]
    if valid_experts is not None and valid_experts < E_total:
        # padding experts (sharding alignment) never receive tokens
        col = jnp.arange(E_total)
        logits = jnp.where(col < valid_experts, logits, -1e9)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, m.top_k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    # GShard/Switch load-balance loss + router z-loss
    E = probs.shape[-1]
    ohot = jax.nn.one_hot(topk_idx[:, 0], E)           # primary choice
    frac_tokens = ohot.mean(axis=0)
    frac_probs = probs.mean(axis=0)
    lb = E * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return RouterOut(topk_idx.astype(jnp.int32), topk_w, probs, lb, z)


class FusedRouting(NamedTuple):
    """Routing plus the dispatch metadata the executors need, in one pass.

    ``pos_in_e`` is each routed (token, k) pair's stable rank among the
    pairs of its expert, in flattened row-major pair order — exactly the
    rank a stable argsort-by-expert assigns, so capacity slots
    (``idx * C + pos_in_e``) and grouped rows
    (``group_offsets[idx] + pos_in_e``) derived from it are bit-equal to
    the :func:`build_dispatch` / :func:`build_grouped_dispatch` plans.
    """

    topk_idx: jnp.ndarray      # (N, k) int32
    topk_weight: jnp.ndarray   # (N, k) f32, normalized
    pos_in_e: jnp.ndarray      # (N, k) int32 stable within-expert rank
    expert_counts: jnp.ndarray  # (E,) int32 routed pair counts
    lb_loss: jnp.ndarray       # scalar
    z_loss: jnp.ndarray        # scalar


def route_fused(router_w: jnp.ndarray, x_flat: jnp.ndarray, m: MoEConfig,
                valid_experts: Optional[int] = None) -> FusedRouting:
    """Single-pass jnp twin of the fused router kernel.

    Same gating math as :func:`route` (identical expressions, so the
    losses and weights match bit-for-bit), but the within-expert ranks
    and per-expert counts come from one exclusive cumsum over the
    one-hot routed pairs instead of the argsort + bincount + cumsum
    passes the separate-pass plan builders run per executor.
    """
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    E = logits.shape[-1]
    if valid_experts is not None and valid_experts < E:
        col = jnp.arange(E)
        logits = jnp.where(col < valid_experts, logits, -1e9)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, m.top_k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    topk_idx = topk_idx.astype(jnp.int32)
    N, k = topk_idx.shape
    # stable within-expert rank via exclusive cumsum of the one-hot pairs
    oh = (topk_idx.reshape(N * k)[:, None]
          == jnp.arange(E, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    csum = jnp.cumsum(oh, axis=0)
    pos_in_e = ((csum - oh) * oh).sum(-1).reshape(N, k)
    counts = oh.sum(0).astype(jnp.int32)
    ohot = jax.nn.one_hot(topk_idx[:, 0], E)           # primary choice
    frac_tokens = ohot.mean(axis=0)
    frac_probs = probs.mean(axis=0)
    lb = E * jnp.sum(frac_tokens * frac_probs)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return FusedRouting(topk_idx, topk_w, pos_in_e, counts, lb, z)


def route_fused_pallas(router_w: jnp.ndarray, x_flat: jnp.ndarray,
                       m: MoEConfig, valid_experts: Optional[int] = None
                       ) -> FusedRouting:
    """Fused routing via the Pallas kernel (compiled on a TPU,
    interpreted elsewhere).

    Integer outputs (indices, ranks, counts) are exact; weights and the
    losses are tolerance-pinned against :func:`route_fused` (the kernel
    reduces the loss statistics tile-by-tile, so float summation order
    differs).
    """
    from repro.kernels.router_topk.ops import router_topk_fused_pallas
    E = router_w.shape[-1]
    N = x_flat.shape[0]
    vals, idx, pos, counts, probs_sum, z_sq = router_topk_fused_pallas(
        x_flat, router_w, k=m.top_k, valid_experts=valid_experts)
    ohot = jax.nn.one_hot(idx[:, 0], E)
    lb = E * jnp.sum(ohot.mean(axis=0) * (probs_sum / N))
    z = z_sq / N
    return FusedRouting(idx, vals, pos, counts.astype(jnp.int32), lb, z)


# ---------------------------------------------------------------------------
# Dispatch plan
# ---------------------------------------------------------------------------

class DispatchPlan(NamedTuple):
    """Scatter/gather indices mapping (token, k)-slots <-> capacity buffers."""

    buffer_index: jnp.ndarray   # (N*k,) int32 flat index into (E*C); E*C if dropped
    token_index: jnp.ndarray    # (N*k,) int32 source token of each sorted slot
    slot_of_pair: jnp.ndarray   # (N, k) int32 flat buffer index per routing pair
    kept: jnp.ndarray           # (N, k) bool, False if dropped by capacity
    expert_counts: jnp.ndarray  # (E,) int32 pre-drop routed counts
    capacity: int


def capacity_for(n_tokens: int, m: MoEConfig, num_experts: int,
                 multiple: int = 8) -> int:
    """Per-expert buffer rows: ceil(n * k * capacity_factor / E), rounded
    up to ``multiple``.

    The ceiling is taken in EXACT rational arithmetic
    (``Fraction(cf).limit_denominator`` recovers the decimal the float
    encodes), so the result never depends on float rounding of the
    ``n * k * cf / E`` product chain: when ``n_tokens * top_k`` divides
    evenly by ``num_experts`` at cf=1.0 a perfectly balanced routing
    fits exactly — no off-by-one row that the multiple round-up would
    inflate into a whole extra tile.
    """
    cf = Fraction(m.capacity_factor).limit_denominator(1 << 16)
    c = max(1, math.ceil(Fraction(n_tokens * m.top_k) * cf / num_experts))
    return ((c + multiple - 1) // multiple) * multiple


class RoutingSummary(NamedTuple):
    """What an executor did with the routed (token, k) pairs.

    Shared across all executors and surfaced through ``aux["routing"]``
    (and, under ``capture``, through the serving telemetry): the planner's
    demand signal counts ROUTED pairs, while ``dropped`` exposes the tax
    the capacity-buffer path silently pays under skew. All leaves are
    arrays so the summary flows through scan/jit capture stacking.
    """

    expert_counts: jnp.ndarray  # (E,) int32 routed pair counts (pre-drop)
    kept_counts: jnp.ndarray    # (E,) int32 pairs actually computed
    dropped: jnp.ndarray        # (E,) int32 pairs dropped by capacity
    drop_mask: jnp.ndarray      # (N, k) bool, True where the pair dropped
    group_offsets: jnp.ndarray  # (E,) int32 first buffer row of each expert
    capacity: jnp.ndarray       # () int32 per-expert capacity (0 = dropless)


def build_dispatch(topk_idx: jnp.ndarray, num_experts: int,
                   capacity: int) -> DispatchPlan:
    N, k = topk_idx.shape
    E, C = num_experts, capacity
    flat_e = topk_idx.reshape(N * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(N * k) - offsets[sorted_e]
    kept_sorted = pos_in_e < C
    buffer_index = jnp.where(kept_sorted, sorted_e * C + pos_in_e, E * C)
    token_index = order // k
    # invert the sort so each (n, k) pair knows its buffer slot
    slot_of_flat = jnp.zeros((N * k,), jnp.int32).at[order].set(
        buffer_index.astype(jnp.int32))
    kept_of_flat = jnp.zeros((N * k,), bool).at[order].set(kept_sorted)
    return DispatchPlan(
        buffer_index=buffer_index.astype(jnp.int32),
        token_index=token_index.astype(jnp.int32),
        slot_of_pair=slot_of_flat.reshape(N, k),
        kept=kept_of_flat.reshape(N, k),
        expert_counts=counts.astype(jnp.int32),
        capacity=C,
    )


def dispatch_plan_from_fused(fr: FusedRouting, num_experts: int,
                             capacity: int) -> DispatchPlan:
    """Capacity-buffer plan straight from fused routing — no argsort.

    ``slot_of_pair = idx * C + pos_in_e`` for kept pairs (rank below
    capacity), the out-of-range sentinel ``E * C`` otherwise; scatter
    destinations are unique, so the buffers built from this plan are
    bit-identical to :func:`build_dispatch`'s (which scatters the same
    values in sorted order).
    """
    N, k = fr.topk_idx.shape
    E, C = num_experts, capacity
    kept = fr.pos_in_e < C
    slot = jnp.where(kept, fr.topk_idx * C + fr.pos_in_e, E * C)
    return DispatchPlan(
        buffer_index=slot.reshape(N * k).astype(jnp.int32),
        token_index=(jnp.arange(N * k, dtype=jnp.int32) // k),
        slot_of_pair=slot.astype(jnp.int32),
        kept=kept,
        expert_counts=fr.expert_counts,
        capacity=C,
    )


def dispatch_tokens(x_flat: jnp.ndarray, plan: DispatchPlan,
                    num_experts: int) -> jnp.ndarray:
    """Scatter tokens into (E, C, d) capacity buffers (dropped -> nowhere)."""
    E, C, d = num_experts, plan.capacity, x_flat.shape[-1]
    buf = jnp.zeros((E * C, d), x_flat.dtype)
    buf = buf.at[plan.buffer_index].set(x_flat[plan.token_index],
                                        mode="drop")
    return buf.reshape(E, C, d)


def combine_tokens(buf_out: jnp.ndarray, plan: DispatchPlan,
                   topk_weight: jnp.ndarray) -> jnp.ndarray:
    """Gather expert outputs back to (N, d), weighted by router probs."""
    E, C, d = buf_out.shape
    flat = buf_out.reshape(E * C, d)
    gathered = flat.at[plan.slot_of_pair].get(mode="fill", fill_value=0.0)
    w = jnp.where(plan.kept, topk_weight, 0.0)
    return jnp.einsum("nkd,nk->nd", gathered, w.astype(gathered.dtype))


# ---------------------------------------------------------------------------
# Grouped (dropless) dispatch: sorted block-aligned ragged groups
# ---------------------------------------------------------------------------

class GroupedDispatch(NamedTuple):
    """Sorted ragged-group layout for the dropless grouped-GEMM path."""

    row_of_pair: jnp.ndarray    # (N, k) int32 destination row per pair
    tile_expert: jnp.ndarray    # (T,) int32 expert owning each row tile
    group_offsets: jnp.ndarray  # (E,) int32 first row of each expert group
    expert_counts: jnp.ndarray  # (E,) int32 routed pair counts
    block_rows: int             # static row-tile height
    num_rows: int               # static padded row count R (T * block_rows)


def grouped_rows_for(n_pairs: int, num_experts: int, block_rows: int = 8,
                     multiple: int = 1) -> int:
    """Static worst-case sorted-buffer rows: every routed pair plus up to
    ``block_rows - 1`` padding rows per ACTIVE expert (at most
    ``min(E, n_pairs)`` experts can be active), tile-aligned."""
    active = min(num_experts, n_pairs)
    worst = n_pairs + active * (block_rows - 1)
    step = block_rows * max(1, multiple)
    return ((worst + step - 1) // step) * step


def build_grouped_dispatch(topk_idx: jnp.ndarray, num_experts: int, *,
                           block_rows: int = 8,
                           row_multiple: int = 1) -> GroupedDispatch:
    """Sort (token, k) pairs by expert into block-aligned ragged groups.

    Each expert's group is padded up to a multiple of ``block_rows`` so
    every row tile belongs to exactly one expert (``tile_expert``) — the
    layout both the jnp blocked fast path and the
    ``repro.kernels.grouped_moe`` Pallas kernel consume. No capacity
    bound: every pair gets a unique destination row (dropless).
    ``row_multiple`` additionally aligns the TOTAL row count (in tiles)
    so the distributed path can split rows into equal pipeline chunks.
    """
    N, k = topk_idx.shape
    E = num_experts
    flat_e = topk_idx.reshape(N * k)
    counts = jnp.bincount(flat_e, length=E)
    padded = ((counts + block_rows - 1) // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    offsets = ends - padded
    R = grouped_rows_for(N * k, E, block_rows, row_multiple)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    raw_off = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(N * k) - raw_off[sorted_e]
    dest_sorted = offsets[sorted_e] + pos_in_e
    row_of_flat = jnp.zeros((N * k,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    # tile t covers rows [t*block_rows, (t+1)*block_rows) — one group each;
    # tiles past the last group clamp to E-1 and hold only zero rows
    tile_start = jnp.arange(R // block_rows) * block_rows
    tile_expert = jnp.clip(
        jnp.searchsorted(ends, tile_start, side="right"), 0, E - 1)
    return GroupedDispatch(
        row_of_pair=row_of_flat.reshape(N, k),
        tile_expert=tile_expert.astype(jnp.int32),
        group_offsets=offsets.astype(jnp.int32),
        expert_counts=counts.astype(jnp.int32),
        block_rows=block_rows,
        num_rows=R,
    )


def grouped_dispatch_from_fused(fr: FusedRouting, num_experts: int, *,
                                block_rows: int = 8,
                                row_multiple: int = 1) -> GroupedDispatch:
    """Block-aligned ragged-group layout straight from fused routing.

    The destination row of a pair is ``group_offsets[expert] + rank``;
    offsets come from one cumsum over the block-padded counts. Bit-equal
    to :func:`build_grouped_dispatch` (which recovers the same ranks via
    a stable argsort).
    """
    N, k = fr.topk_idx.shape
    E = num_experts
    counts = fr.expert_counts
    padded = ((counts + block_rows - 1) // block_rows) * block_rows
    ends = jnp.cumsum(padded)
    offsets = ends - padded
    R = grouped_rows_for(N * k, E, block_rows, row_multiple)
    row_of_pair = offsets[fr.topk_idx] + fr.pos_in_e
    tile_start = jnp.arange(R // block_rows) * block_rows
    tile_expert = jnp.clip(
        jnp.searchsorted(ends, tile_start, side="right"), 0, E - 1)
    return GroupedDispatch(
        row_of_pair=row_of_pair.astype(jnp.int32),
        tile_expert=tile_expert.astype(jnp.int32),
        group_offsets=offsets.astype(jnp.int32),
        expert_counts=counts.astype(jnp.int32),
        block_rows=block_rows,
        num_rows=R,
    )


def dispatch_grouped(x_flat: jnp.ndarray, gd: GroupedDispatch) -> jnp.ndarray:
    """Scatter tokens into the sorted (R, d) ragged-group buffer."""
    d = x_flat.shape[-1]
    N, k = gd.row_of_pair.shape
    tok = jnp.arange(N * k) // k
    buf = jnp.zeros((gd.num_rows, d), x_flat.dtype)
    return buf.at[gd.row_of_pair.reshape(-1)].set(x_flat[tok])


def combine_grouped(buf_out: jnp.ndarray, gd: GroupedDispatch,
                    topk_weight: jnp.ndarray) -> jnp.ndarray:
    """Gather every pair's expert output (dropless) and mix by router
    weight."""
    g = buf_out[gd.row_of_pair]                      # (N, k, d)
    return jnp.einsum("nkd,nk->nd", g, topk_weight.astype(g.dtype))


def grouped_expert_ffn(params: Params, buf: jnp.ndarray,
                       tile_expert: jnp.ndarray,
                       activation: str) -> jnp.ndarray:
    """jnp fast path: blocked grouped GEMM over (T, block_rows, d) tiles.

    Gathers each tile's expert weights and contracts per tile — the same
    ragged layout (and cost ∝ routed tokens) as the Pallas kernel, with
    f32 accumulation. ``repro.kernels.grouped_moe.moe_grouped_ffn_adapter``
    is the drop-in kernel replacement.
    """
    R, d = buf.shape
    T = tile_expert.shape[0]
    xb = buf.reshape(T, R // T, d).astype(jnp.float32)
    if activation == "swiglu":
        wg = params["w_gate"][tile_expert].astype(jnp.float32)
        wu = params["w_up"][tile_expert].astype(jnp.float32)
        g = jnp.einsum("tbd,tdf->tbf", xb, wg)
        u = jnp.einsum("tbd,tdf->tbf", xb, wu)
        h = jax.nn.silu(g) * u
        wd = params["w_down"][tile_expert].astype(jnp.float32)
    else:
        wi = params["w_in"][tile_expert].astype(jnp.float32)
        h = jax.nn.gelu(jnp.einsum("tbd,tdf->tbf", xb, wi))
        wd = params["w_out"][tile_expert].astype(jnp.float32)
    out = jnp.einsum("tbf,tfd->tbd", h, wd)
    return out.reshape(R, d).astype(buf.dtype)


# ---------------------------------------------------------------------------
# Expert FFN on capacity buffers
# ---------------------------------------------------------------------------

def expert_ffn(params: Params, buf: jnp.ndarray, activation: str) -> jnp.ndarray:
    """buf: (E, C, d) -> (E, C, d); batched over experts."""
    if activation == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        h = jax.nn.silu(g) * u
        return jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, params["w_in"]))
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"])


# ---------------------------------------------------------------------------
# Full layer
# ---------------------------------------------------------------------------

def _all_experts_out(params: Params, activation: str,
                     x_flat: jnp.ndarray) -> jnp.ndarray:
    """(E, N, d): every expert applied to every token (oracle compute)."""
    if activation == "swiglu":
        g = jnp.einsum("nd,edf->enf", x_flat, params["w_gate"])
        u = jnp.einsum("nd,edf->enf", x_flat, params["w_up"])
        h = jax.nn.silu(g) * u
        return jnp.einsum("enf,efd->end", h, params["w_down"])
    h = jax.nn.gelu(jnp.einsum("nd,edf->enf", x_flat, params["w_in"]))
    return jnp.einsum("enf,efd->end", h, params["w_out"])


def _dropless_summary(counts: jnp.ndarray, drop_mask_shape: Tuple[int, int],
                      group_offsets: jnp.ndarray) -> RoutingSummary:
    return RoutingSummary(
        expert_counts=counts,
        kept_counts=counts,
        dropped=jnp.zeros_like(counts),
        drop_mask=jnp.zeros(drop_mask_shape, bool),
        group_offsets=group_offsets.astype(jnp.int32),
        capacity=jnp.int32(0),
    )


def moe_forward(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                *, executor: str = "dense", capture: bool = False,
                expert_ffn_fn=None, grouped_ffn_fn=None,
                block_rows: int = 8, router_impl: str = "fused"
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Local (data-parallel) MoE layer. x: (B, S, d).

    ``executor`` selects the dispatch path (see module docstring):
    ``"dense"`` capacity buffers (may drop tokens), ``"grouped"`` dropless
    ragged grouped GEMM, ``"oracle"`` all-experts reference.
    ``router_impl`` selects the routing front-end (``ROUTER_IMPLS``): the
    default single-pass ``"fused"`` twin, the separate-pass
    ``"reference"``, or the ``"pallas"`` kernel — all three feed every
    executor through the same dispatch layouts (integers bit-equal).
    ``expert_ffn_fn`` / ``grouped_ffn_fn`` swap in the Pallas kernels for
    the dense / grouped expert compute respectively. ``aux["routing"]``
    always carries the executor's :class:`RoutingSummary`.
    """
    m = cfg.moe
    assert m is not None
    if executor not in MOE_EXECUTORS:
        raise ValueError(f"unknown MoE executor {executor!r}; "
                         f"expected one of {MOE_EXECUTORS}")
    if router_impl not in ROUTER_IMPLS:
        raise ValueError(f"unknown router impl {router_impl!r}; "
                         f"expected one of {ROUTER_IMPLS}")
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    with jax.named_scope("router"):
        if router_impl == "reference":
            r = route(params["router"], x_flat, m,
                      valid_experts=m.num_experts)
            fr = None
        elif router_impl == "pallas":
            r = fr = route_fused_pallas(params["router"], x_flat, m,
                                        valid_experts=m.num_experts)
        else:
            r = fr = route_fused(params["router"], x_flat, m,
                                 valid_experts=m.num_experts)
    E = params["router"].shape[-1]

    if executor == "dense":
        C = capacity_for(B * S, m, E)
        with jax.named_scope("dispatch"):
            plan = (build_dispatch(r.topk_idx, E, C) if fr is None
                    else dispatch_plan_from_fused(fr, E, C))
            buf = dispatch_tokens(x_flat, plan, E)
        with jax.named_scope("experts"):
            fn = expert_ffn_fn or expert_ffn
            buf_out = fn(params, buf, cfg.activation)
        with jax.named_scope("combine"):
            y = combine_tokens(buf_out, plan, r.topk_weight)
        counts = plan.expert_counts
        kept = jnp.minimum(counts, C)    # sort-based: first C per expert
        summary = RoutingSummary(
            expert_counts=counts,
            kept_counts=kept,
            dropped=counts - kept,
            drop_mask=~plan.kept,
            group_offsets=jnp.arange(E, dtype=jnp.int32) * C,
            capacity=jnp.int32(C),
        )
    elif executor == "grouped":
        with jax.named_scope("dispatch"):
            gd = (build_grouped_dispatch(r.topk_idx, E,
                                         block_rows=block_rows)
                  if fr is None else
                  grouped_dispatch_from_fused(fr, E, block_rows=block_rows))
            buf = dispatch_grouped(x_flat, gd)
        with jax.named_scope("experts"):
            fn = grouped_ffn_fn or grouped_expert_ffn
            buf_out = fn(params, buf, gd.tile_expert, cfg.activation)
        with jax.named_scope("combine"):
            y = combine_grouped(buf_out, gd, r.topk_weight)
        summary = _dropless_summary(gd.expert_counts,
                                    (B * S, m.top_k), gd.group_offsets)
    else:  # oracle
        all_out = _all_experts_out(params, cfg.activation, x_flat)
        sel = jnp.take_along_axis(
            jnp.moveaxis(all_out, 0, 1), r.topk_idx[..., None], axis=1)
        y = jnp.einsum("nkd,nk->nd", sel, r.topk_weight.astype(sel.dtype))
        counts = (jnp.bincount(r.topk_idx.reshape(-1),
                               length=E).astype(jnp.int32)
                  if fr is None else fr.expert_counts)
        summary = _dropless_summary(counts, (B * S, m.top_k),
                                    jnp.cumsum(counts) - counts)

    if m.num_shared_experts > 0:
        with jax.named_scope("experts"):
            y = y + mlp_forward(params["shared"], x_flat, cfg.activation)
    aux: Dict[str, jnp.ndarray] = {
        "lb_loss": r.lb_loss * m.router_aux_coef,
        "z_loss": r.z_loss * m.router_z_coef,
        "expert_counts": summary.expert_counts,
        "routing": summary,
    }
    if capture:
        aux["topk_idx"] = r.topk_idx.reshape(B, S, m.top_k)
        aux["topk_weight"] = r.topk_weight.reshape(B, S, m.top_k)
    return y.reshape(B, S, d).astype(x.dtype), aux


def moe_forward_oracle(params: Params, cfg: ModelConfig,
                       x: jnp.ndarray) -> jnp.ndarray:
    """Reference: every expert computed for every token, then top-k mixed.

    O(N * E * ff) -- only for tests. No capacity dropping, so it matches
    the dense executor exactly only when capacity_factor admits every
    token; the grouped executor matches it for EVERY routing.
    """
    m = cfg.moe
    assert m is not None
    B, S, d = x.shape
    x_flat = x.reshape(B * S, d)
    r = route(params["router"], x_flat, m)
    all_out = _all_experts_out(params, cfg.activation, x_flat)
    # all_out: (E, N, d); select top-k
    sel = jnp.take_along_axis(
        jnp.moveaxis(all_out, 0, 1), r.topk_idx[..., None], axis=1)  # (N,k,d)
    y = jnp.einsum("nkd,nk->nd", sel, r.topk_weight.astype(sel.dtype))
    if m.num_shared_experts > 0:
        y = y + mlp_forward(params["shared"], x_flat, cfg.activation)
    return y.reshape(B, S, d).astype(x.dtype)
