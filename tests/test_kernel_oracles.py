"""Every Pallas kernel vs its pure-jnp oracle, through ONE harness.

The differential grid lives in ``tests/kernel_harness.py`` (shapes x
dtypes x block sizes under a single tolerance table); this module
materializes it, keeps the kernel<->model integration checks, and pins
the capacity-edge regressions (``expert_ffn_pallas`` sub-sublane
capacities, exact ``capacity_for``). Replaces the ad-hoc per-kernel
checks that used to live in ``tests/test_kernels.py``.
"""
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.decode_attention.ops import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.expert_ffn.ops import (aligned_block, expert_ffn_pallas,
                                          moe_expert_ffn_adapter)
from repro.kernels.expert_ffn.ref import expert_ffn_ref
from repro.kernels.grouped_moe.ops import grouped_moe_pallas
from repro.kernels.grouped_moe.ref import grouped_moe_ref
from repro.kernels.router_topk.ops import router_topk_pallas

from kernel_harness import all_cases, grouped_inputs, run_case

CASES = all_cases()


# ---------------------------------------------------------------------------
# the unified differential grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", CASES, ids=[c.id for c in CASES])
def test_kernel_matches_oracle(case):
    run_case(case)


def test_grid_covers_every_kernel():
    """The harness must exercise every kernel package in both dtypes."""
    seen = {(c.kernel, jnp.dtype(c.dtype).name) for c in CASES}
    for kernel in ("expert_ffn", "grouped_moe", "router_topk",
                   "decode_attention"):
        for dt in ("float32", "bfloat16"):
            assert (kernel, dt) in seen, f"missing {kernel}/{dt} coverage"


# ---------------------------------------------------------------------------
# grouped_moe semantics beyond allclose
# ---------------------------------------------------------------------------

def test_grouped_moe_zero_padding_rows_stay_zero():
    """Group-padding rows (zeros) must produce exactly zero output."""
    x, te, wg, wu, wd = grouped_inputs((5, 0, 11, 1), 16, 24)
    out = grouped_moe_pallas(x, te, wg, wu, wd)
    zero_rows = ~np.asarray(jnp.abs(x).sum(-1) > 0)
    assert float(jnp.abs(jnp.asarray(out)[zero_rows]).max()) == 0.0


def test_grouped_moe_tile_indirection_uses_right_weights():
    """Scaling ONE expert's weights must change only its own tiles."""
    counts = (8, 8, 8)
    x, te, wg, wu, wd = grouped_inputs(counts, 16, 24)
    base = np.asarray(grouped_moe_pallas(x, te, wg, wu, wd))
    wd2 = wd.at[1].multiply(2.0)
    out = np.asarray(grouped_moe_pallas(x, te, wg, wu, wd2))
    rows_e1 = slice(8, 16)
    np.testing.assert_allclose(out[rows_e1], 2.0 * base[rows_e1],
                               rtol=1e-5, atol=1e-6)
    mask = np.ones(len(out), bool)
    mask[rows_e1] = False
    np.testing.assert_array_equal(out[mask], base[mask])


@settings(max_examples=10, deadline=None)
@given(E=st.integers(1, 6), C=st.sampled_from([32, 72, 130]),
       D=st.sampled_from([16, 48]), F=st.sampled_from([24, 64]))
def test_expert_ffn_ragged_shapes(E, C, D, F):
    """Non-multiple C/F exercise the dense kernel's padding path."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    buf = 0.5 * jax.random.normal(ks[0], (E, C, D))
    wg = 0.2 * jax.random.normal(ks[1], (E, D, F))
    wu = 0.2 * jax.random.normal(ks[2], (E, D, F))
    wd = 0.2 * jax.random.normal(ks[3], (E, F, D))
    got = expert_ffn_pallas(buf, wg, wu, wd, block_c=64, block_f=32)
    want = expert_ffn_ref(buf, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=8, deadline=None)
@given(T=st.sampled_from([96, 500, 1024]), valid=st.integers(1, 96),
       seed=st.integers(0, 50))
def test_decode_attention_random_valid_lengths(T, valid, seed):
    """Random (cache length, valid prefix) pairs exercise the masking."""
    B, N, G, D = 1, 2, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, N, G, D))
    k = jax.random.normal(ks[1], (B, T, N, D))
    v = jax.random.normal(ks[2], (B, T, N, D))
    got = decode_attention_pallas(q, k, v, valid, block_t=128)
    want = decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), e=st.integers(1, 6))
def test_grouped_moe_property_random_groups(seed, e):
    rng = np.random.default_rng(seed)
    counts = tuple(int(c) for c in rng.integers(0, 40, size=e))
    if sum(counts) == 0:
        counts = counts[:-1] + (3,)
    x, te, wg, wu, wd = grouped_inputs(counts, 16, 24, seed=seed)
    got = grouped_moe_pallas(x, te, wg, wu, wd, block_f=16)
    want = grouped_moe_ref(x, te, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# capacity-edge regressions (dense path)
# ---------------------------------------------------------------------------

def test_aligned_block_always_sublane_multiple():
    """REGRESSION: the old clamp min(block, max(C, 8)) emitted misaligned
    row blocks for 8 < C < block (e.g. C=12 -> 12) and honored sub-8
    requests — Mosaic tiling violations on a real TPU."""
    for dim in range(1, 40):
        for block in (1, 2, 4, 6, 8, 12, 64, 128):
            b = aligned_block(block, dim)
            assert b % 8 == 0, (dim, block, b)
            assert b <= ((min(block, dim) + 7) // 8) * 8


@pytest.mark.parametrize("C", [1, 2, 3, 5, 7, 12])
@pytest.mark.parametrize("block_c", [128, 4])
def test_expert_ffn_sub_sublane_capacity(C, block_c):
    """REGRESSION: capacities below one sublane tile (C < 8) and
    misaligned explicit blocks must round-trip through the padding path
    bit-compatibly with the oracle."""
    E, D, F = 3, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    buf = 0.5 * jax.random.normal(ks[0], (E, C, D))
    wg = 0.2 * jax.random.normal(ks[1], (E, D, F))
    wu = 0.2 * jax.random.normal(ks[2], (E, D, F))
    wd = 0.2 * jax.random.normal(ks[3], (E, F, D))
    got = expert_ffn_pallas(buf, wg, wu, wd, block_c=block_c)
    want = expert_ffn_ref(buf, wg, wu, wd)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_capacity_for_exact_on_even_division():
    """REGRESSION: when n_tokens * top_k divides evenly by num_experts at
    cf=1.0, a perfectly balanced routing must fit EXACTLY — the old
    int(...)+1 added a phantom row that the multiple-of-8 round-up
    inflated into a whole extra tile (16 instead of 8 for 64 pairs over
    8 experts)."""
    from repro.config import MoEConfig
    from repro.models.moe import capacity_for

    for n, k, e in [(32, 2, 8), (16, 2, 4), (64, 1, 8), (120, 4, 60)]:
        m = MoEConfig(num_experts=e, top_k=k, d_expert_ff=8,
                      capacity_factor=1.0)
        balanced = n * k // e
        want = ((balanced + 7) // 8) * 8
        assert capacity_for(n, m, e) == want, (n, k, e)


def test_capacity_for_float_chain_determinism():
    """REGRESSION: int(n*k*cf/e) depended on float rounding of the
    product chain (e.g. 5*1.2/2 -> 3.0000000000000004). The exact
    rational ceiling must agree with decimal arithmetic everywhere."""
    from repro.config import MoEConfig
    from repro.models.moe import capacity_for

    for n in range(1, 200):
        for k in (1, 2, 4):
            for e in (2, 4, 8, 60):
                for cf in (1.0, 1.1, 1.2, 1.25, 0.6):
                    m = MoEConfig(num_experts=e, top_k=k, d_expert_ff=8,
                                  capacity_factor=cf)
                    exact = math.ceil(
                        Fraction(n * k)
                        * Fraction(cf).limit_denominator(1 << 16) / e)
                    want = ((max(1, exact) + 7) // 8) * 8
                    assert capacity_for(n, m, e) == want, (n, k, e, cf)


# ---------------------------------------------------------------------------
# kernel <-> model integration (ported from the old test_kernels.py)
# ---------------------------------------------------------------------------

def test_expert_ffn_zero_slots_stay_zero():
    """Empty capacity slots (zeros) must produce exactly zero output."""
    E, C, D, F = 2, 64, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    out = expert_ffn_pallas(jnp.zeros((E, C, D)),
                            jax.random.normal(ks[0], (E, D, F)),
                            jax.random.normal(ks[1], (E, D, F)),
                            jax.random.normal(ks[2], (E, F, D)))
    assert float(jnp.abs(out).max()) == 0.0


def test_expert_ffn_matches_model_layer():
    """The dense kernel is a drop-in for the model's expert_ffn."""
    from repro.models.moe import expert_ffn
    E, C, D, F = 4, 64, 32, 48
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {"w_gate": 0.2 * jax.random.normal(ks[0], (E, D, F)),
              "w_up": 0.2 * jax.random.normal(ks[1], (E, D, F)),
              "w_down": 0.2 * jax.random.normal(ks[2], (E, F, D))}
    buf = 0.5 * jax.random.normal(ks[3], (E, C, D))
    np.testing.assert_allclose(
        np.asarray(moe_expert_ffn_adapter(params, buf, "swiglu")),
        np.asarray(expert_ffn(params, buf, "swiglu")),
        rtol=2e-5, atol=2e-5)


def test_grouped_kernel_matches_model_grouped_ffn():
    """The grouped kernel is a drop-in for the model's jnp fast path on a
    REAL dispatch built from skewed routing."""
    from repro.kernels.grouped_moe.ops import moe_grouped_ffn_adapter
    from repro.models.moe import (build_grouped_dispatch, dispatch_grouped,
                                  grouped_expert_ffn)
    from repro.traces import zipf_routing
    E, D, F, N, k = 6, 16, 24, 50, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    params = {"w_gate": 0.2 * jax.random.normal(ks[0], (E, D, F)),
              "w_up": 0.2 * jax.random.normal(ks[1], (E, D, F)),
              "w_down": 0.2 * jax.random.normal(ks[2], (E, F, D))}
    topk = jnp.asarray(zipf_routing(N, E, k, alpha=1.2))
    gd = build_grouped_dispatch(topk, E)
    buf = dispatch_grouped(jax.random.normal(ks[3], (N, D)), gd)
    got = moe_grouped_ffn_adapter(params, buf, gd.tile_expert, "swiglu")
    want = grouped_expert_ffn(params, buf, gd.tile_expert, "swiglu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_router_topk_respects_valid_experts():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    _, idx = router_topk_pallas(x, w, k=4, valid_experts=60)
    assert int(idx.max()) < 60


@settings(max_examples=10, deadline=None)
@given(N=st.integers(1, 300), E=st.integers(2, 64), seed=st.integers(0, 99))
def test_router_topk_weights_normalized(N, E, seed):
    k = min(2, E)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    vals, idx = router_topk_pallas(jax.random.normal(ks[0], (N, 32)),
                                   jax.random.normal(ks[1], (32, E)), k=k)
    np.testing.assert_allclose(np.asarray(vals.sum(-1)), 1.0, rtol=1e-5)
    assert (np.asarray(idx) < E).all()
    if k == 2:
        assert (np.asarray(vals[:, 0]) >= np.asarray(vals[:, 1]) - 1e-6).all()


def test_decode_attention_per_batch_valid_lengths():
    B, N, G, D, T = 3, 2, 2, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, N, G, D))
    k = jax.random.normal(ks[1], (B, T, N, D))
    v = jax.random.normal(ks[2], (B, T, N, D))
    valid = jnp.array([1, 100, 256], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(decode_attention_pallas(q, k, v, valid)),
        np.asarray(decode_attention_ref(q, k, v, valid)),
        rtol=2e-5, atol=2e-5)


def test_decode_attention_ignores_invalid_slots():
    """Garbage beyond valid_len must not affect the output."""
    B, N, G, D, T = 1, 1, 2, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, N, G, D))
    k = jax.random.normal(ks[1], (B, T, N, D))
    v = jax.random.normal(ks[2], (B, T, N, D))
    valid = 64
    out1 = decode_attention_pallas(q, k, v, valid)
    out2 = decode_attention_pallas(q, k.at[:, valid:].set(1e4),
                                   v.at[:, valid:].set(-1e4), valid)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)


def test_router_fused_padded_rows_inert():
    """REGRESSION: ``router_topk_pallas`` zero-pads token rows up to
    ``block_n``; the padded rows used to flow through softmax/top-k like
    real tokens. The fused kernel's routing statistics make the bug
    observable: expert counts must cover exactly the N*k LIVE pairs and
    the probability/z-loss sufficient statistics must match the pure-jnp
    values computed over real rows only."""
    from repro.kernels.router_topk.ops import router_topk_fused_pallas
    N, D, E, k, bn = 100, 32, 16, 4, 64          # N % bn != 0
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (N, D))
    w = jax.random.normal(ks[1], (D, E))
    vals, idx, pos, counts, psum, zsq = router_topk_fused_pallas(
        x, w, k=k, block_n=bn)
    counts = np.asarray(counts)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(idx).ravel(), minlength=E),
        err_msg="counts must cover live (token, k) pairs only")
    assert counts.sum() == N * k
    logits = np.asarray(x @ w, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(psum), probs.sum(0),
                               rtol=1e-4, atol=1e-4)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True))
                 .sum(-1)) + logits.max(-1)
    np.testing.assert_allclose(float(zsq), float((lse ** 2).sum()),
                               rtol=1e-3)
    # pos_in_e is the row-major arrival rank within each expert: the
    # ranks of each expert's pairs must be exactly 0..count-1
    pos, idx = np.asarray(pos), np.asarray(idx)
    for e in range(E):
        ranks = np.sort(pos.ravel()[idx.ravel() == e])
        np.testing.assert_array_equal(ranks, np.arange(counts[e]),
                                      err_msg=f"expert {e} ranks")


def test_router_fused_matches_jnp_fused_twin():
    """The Pallas fused router must agree with the pure-jnp fused twin
    (``route_fused``) on indices and arrival ranks EXACTLY, and on
    weights within kernel tolerance — including at N % block_n != 0."""
    from repro.kernels.router_topk.ops import router_topk_fused_pallas
    from repro.models.moe import route_fused
    N, D, E, k = 100, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (N, D))
    w = jax.random.normal(ks[1], (D, E))
    m = type("M", (), {"num_experts": E, "top_k": k,
                       "norm_topk_prob": True})()
    fr = route_fused(w, x, m)
    vals, idx, pos, counts, _, _ = router_topk_fused_pallas(
        x, w, k=k, block_n=64)
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.asarray(fr.topk_idx))
    np.testing.assert_array_equal(np.asarray(pos),
                                  np.asarray(fr.pos_in_e))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(fr.expert_counts))
    np.testing.assert_allclose(np.asarray(vals),
                               np.asarray(fr.topk_weight),
                               rtol=1e-4, atol=1e-5)


def test_decode_attention_matches_flash_twin_ragged():
    """Per-slot ragged valid lengths at T % block_t != 0 vs the model's
    pure-jnp flash twin — the exact shape the serving engine decodes."""
    from repro.models.attention import _flash_attend
    B, N, G, D, T = 3, 2, 2, 32, 640
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, N, G, 1, D))
    k = jax.random.normal(ks[1], (B, N, T, D))
    v = jax.random.normal(ks[2], (B, N, T, D))
    valid = jnp.asarray([7, 301, 640], jnp.int32)
    want, _ = _flash_attend(q, k, v, causal=False, window=0,
                            q_offset=jnp.asarray(0), kv_valid_len=valid)
    got = decode_attention_pallas(
        q[:, :, :, 0], jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        valid, block_t=256)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, :, :, 0]),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_matches_flash_twin_sliding_window():
    """A sliding-window layer's rolling cache reduces to slot validity
    at decode (the window IS the cache): per-row valid = min(pos+1, W).
    The kernel must agree with the flash twin on a partially wrapped
    rolling cache, W % block_t != 0."""
    from repro.models.attention import _flash_attend
    B, N, G, D, W = 2, 2, 2, 32, 96
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, N, G, 1, D))
    k = jax.random.normal(ks[1], (B, N, W, D))
    v = jax.random.normal(ks[2], (B, N, W, D))
    # row 0 wrapped (pos >= W: whole cache live), row 1 still filling
    valid = jnp.asarray([W, 40], jnp.int32)
    want, _ = _flash_attend(q, k, v, causal=False, window=0,
                            q_offset=jnp.asarray(0), kv_valid_len=valid)
    got = decode_attention_pallas(
        q[:, :, :, 0], jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        valid, block_t=64)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, :, :, 0]),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_matches_model_attention():
    """Kernel agrees with the model's decode path (same masking rules)."""
    from repro.models.attention import _flash_attend
    B, N, G, D, T = 2, 2, 2, 32, 512
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, N, G, 1, D))      # model: (B,N,G,S,D)
    k = jax.random.normal(ks[1], (B, N, T, D))         # model: (B,N,T,D)
    v = jax.random.normal(ks[2], (B, N, T, D))
    valid = 300
    want, _ = _flash_attend(q, k, v, causal=False, window=0,
                            q_offset=jnp.asarray(0), kv_valid_len=valid)
    got = decode_attention_pallas(
        q[:, :, :, 0], jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2), valid)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want[:, :, :, 0]),
                               rtol=3e-5, atol=3e-5)
