"""Unified kernel-oracle differential harness.

One declarative case table covers EVERY Pallas kernel in
``repro.kernels`` (``router_topk``, ``expert_ffn``, ``decode_attention``,
``grouped_moe``): each :class:`KernelCase` builds pinned-seed inputs,
runs the jit'd Pallas wrapper (interpreted off a TPU) and its
``ref.py`` oracle, and compares under ONE parameterized tolerance table
(dtype x comparison kind). ``tests/test_kernel_oracles.py`` materializes
the grid; benchmarks reuse ``run_case`` for their parity checks.

Adding a kernel = appending cases to ``all_cases()``. The harness keeps
tolerances in one place so a dtype's bound can't silently diverge
between ad-hoc per-kernel tests.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.decode_attention.ops import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.expert_ffn.ops import expert_ffn_pallas
from repro.kernels.expert_ffn.ref import expert_ffn_ref
from repro.kernels.grouped_moe.ops import grouped_moe_pallas
from repro.kernels.grouped_moe.ref import grouped_moe_ref
from repro.kernels.router_topk.ops import router_topk_pallas
from repro.kernels.router_topk.ref import router_topk_ref

# one tolerance table for every kernel: (rtol, atol) by dtype
TOLERANCES: Dict[str, Dict[str, Tuple[float, float]]] = {
    "float32": {"allclose": (3e-5, 3e-5)},
    "bfloat16": {"allclose": (2e-2, 2e-2)},
}


def tol_for(dtype) -> Dict[str, float]:
    rtol, atol = TOLERANCES[jnp.dtype(dtype).name]["allclose"]
    return {"rtol": rtol, "atol": atol}


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One differential check: kernel vs oracle on pinned random inputs."""

    kernel: str                       # repro.kernels package name
    label: str                        # unique id suffix (shape/blocks)
    make: Callable[[], tuple]         # () -> (args, kwargs)
    run: Callable[..., object]        # Pallas wrapper
    ref: Callable[..., object]        # pure-jnp oracle
    dtype: object = jnp.float32
    exact_idx: Optional[int] = None   # output index compared exactly (ints)
    # per-case override of the shared tolerance table (e.g. router_topk
    # compares softmax PROBABILITIES, computed in f32 for every input
    # dtype, so its bound is dtype-independent)
    tol: Optional[Dict[str, float]] = None

    @property
    def id(self) -> str:
        return f"{self.kernel}-{self.label}-{jnp.dtype(self.dtype).name}"


# kernel-implementation knobs the pure-jnp oracles never see
_KERNEL_ONLY = ("interpret", "block_c", "block_f", "block_t", "block_n",
                "block_rows")


def run_case(case: KernelCase) -> None:
    """Execute one case; raises AssertionError with the case id on drift."""
    args, kwargs = case.make()
    got = case.run(*args, **kwargs)
    want = case.ref(*args, **{k: v for k, v in kwargs.items()
                              if k not in _KERNEL_ONLY})
    if not isinstance(got, tuple):
        got, want = (got,), (want,)
    assert len(got) == len(want), case.id
    for i, (g, w) in enumerate(zip(got, want)):
        if case.exact_idx is not None and i == case.exact_idx:
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"{case.id}: exact output {i} drifted")
        else:
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32),
                **(case.tol or tol_for(case.dtype)),
                err_msg=f"{case.id}: output {i} outside tolerance")


# ---------------------------------------------------------------------------
# Case builders (pinned seeds; every sampled weight scaled for f32 headroom)
# ---------------------------------------------------------------------------

def _expert_ffn_case(E, C, D, F, dtype, activation, label, **blocks):
    def make():
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        buf = (0.5 * jax.random.normal(ks[0], (E, C, D))).astype(dtype)
        wg = (0.2 * jax.random.normal(ks[1], (E, D, F))).astype(dtype)
        wu = ((0.2 * jax.random.normal(ks[2], (E, D, F))).astype(dtype)
              if activation == "swiglu" else None)
        wd = (0.2 * jax.random.normal(ks[3], (E, F, D))).astype(dtype)
        return (buf, wg, wu, wd), {"activation": activation, **blocks}
    return KernelCase("expert_ffn", label, make, expert_ffn_pallas,
                      expert_ffn_ref, dtype)


def grouped_inputs(counts, D, F, dtype=jnp.float32, block_rows=8, seed=0):
    """Sorted ragged-group buffer from per-expert row counts (the layout
    ``build_grouped_dispatch`` emits): real rows are random, group padding
    rows are zero, ``tile_expert`` maps each row tile to its owner."""
    E = len(counts)
    ks = jax.random.split(jax.random.PRNGKey(seed), E + 3)
    rows, tiles = [], []
    for e, c in enumerate(counts):
        if c == 0:
            continue
        pad = (-c) % block_rows
        rows.append(0.5 * jax.random.normal(ks[e], (c, D)))
        if pad:
            rows.append(jnp.zeros((pad, D)))
        tiles += [e] * ((c + pad) // block_rows)
    x_sorted = jnp.concatenate(rows).astype(dtype)
    tile_expert = jnp.asarray(tiles, jnp.int32)
    wg = (0.2 * jax.random.normal(ks[E], (E, D, F))).astype(dtype)
    wu = (0.2 * jax.random.normal(ks[E + 1], (E, D, F))).astype(dtype)
    wd = (0.2 * jax.random.normal(ks[E + 2], (E, F, D))).astype(dtype)
    return x_sorted, tile_expert, wg, wu, wd


def _grouped_moe_case(counts, D, F, dtype, activation, label, **blocks):
    def make():
        x, te, wg, wu, wd = grouped_inputs(tuple(counts), D, F, dtype)
        if activation != "swiglu":
            wu = None
        return (x, te, wg, wu, wd), {"activation": activation, **blocks}
    return KernelCase("grouped_moe", label, make, grouped_moe_pallas,
                      grouped_moe_ref, dtype)


def _router_case(N, D, E, k, dtype, label, **kwargs):
    def make():
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        x = jax.random.normal(ks[0], (N, D)).astype(dtype)
        w = jax.random.normal(ks[1], (D, E)).astype(dtype)
        return (x, w), {"k": k, **kwargs}
    return KernelCase("router_topk", label, make,
                      router_topk_pallas,
                      lambda x, w, k: router_topk_ref(x, w, k),
                      dtype, exact_idx=1,          # indices compare exactly
                      tol={"rtol": 1e-4, "atol": 1e-5})


def _decode_attn_case(B, N, G, D, T, valid, dtype, label, **blocks):
    def make():
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, N, G, D)).astype(dtype)
        k = jax.random.normal(ks[1], (B, T, N, D)).astype(dtype)
        v = jax.random.normal(ks[2], (B, T, N, D)).astype(dtype)
        return (q, k, v, valid), dict(blocks)
    return KernelCase("decode_attention", label, make,
                      decode_attention_pallas, decode_attention_ref, dtype)


def all_cases():
    """The full differential grid: every kernel x shape x dtype x blocks."""
    cases = []
    for dtype in (jnp.float32, jnp.bfloat16):
        # expert_ffn: aligned, ragged-padding, and sub-sublane capacities
        for E, C, D, F in [(4, 128, 64, 128), (2, 256, 128, 256),
                           (8, 64, 32, 96), (1, 128, 256, 512)]:
            for act in ("swiglu", "gelu"):
                cases.append(_expert_ffn_case(
                    E, C, D, F, dtype, act, f"E{E}C{C}D{D}F{F}-{act}"))
        cases.append(_expert_ffn_case(3, 72, 48, 40, dtype, "swiglu",
                                      "ragged-b64x32", block_c=64,
                                      block_f=32))
        # grouped_moe: balanced, skewed, one-expert-takes-all, empty groups
        for counts, label in [((8, 8, 8, 8), "balanced"),
                              ((27, 3, 1, 0, 0, 1), "skewed"),
                              ((0, 64, 0, 0), "all-to-one")]:
            for act in ("swiglu", "gelu"):
                cases.append(_grouped_moe_case(
                    counts, 32, 48, dtype, act, f"{label}-{act}"))
        cases.append(_grouped_moe_case((13, 5, 90, 2), 16, 24, dtype,
                                       "swiglu", "skewed-bf16", block_f=16))
        # router_topk
        for N, D, E, k in [(256, 64, 8, 2), (128, 32, 60, 4),
                           (512, 128, 16, 1), (100, 48, 40, 8)]:
            cases.append(_router_case(N, D, E, k, dtype,
                                      f"N{N}D{D}E{E}k{k}"))
        # REGRESSION (padded-row inertness): N % block_n != 0 with an
        # EXPLICIT block smaller than N — the zero-padded tail rows used
        # to flow through softmax/top-k alongside real rows
        cases.append(_router_case(100, 48, 40, 8, dtype, "pad-b64",
                                  block_n=64))
        cases.append(_router_case(130, 32, 8, 2, dtype, "pad-b32",
                                  block_n=32))
        # decode_attention
        for B, N, G, D, T in [(2, 2, 4, 64, 1024), (1, 8, 1, 128, 512),
                              (4, 1, 2, 32, 2048), (2, 4, 4, 64, 640)]:
            cases.append(_decode_attn_case(B, N, G, D, T, T - 17, dtype,
                                           f"B{B}N{N}G{G}D{D}T{T}"))
        cases.append(_decode_attn_case(1, 2, 2, 32, 500, 96, dtype,
                                       "short-b128", block_t=128))
        # per-slot RAGGED valid lengths (the serving engine's decode
        # shape) at T % block_t != 0, so tail-tile padding and per-row
        # masking compose
        cases.append(_decode_attn_case(
            3, 2, 2, 32, 640, jnp.asarray([5, 300, 640], jnp.int32),
            dtype, "ragged-T640-b256", block_t=256))
        cases.append(_decode_attn_case(
            4, 1, 2, 32, 384, jnp.asarray([1, 64, 200, 384], jnp.int32),
            dtype, "ragged-T384-b256", block_t=256))
    return cases
