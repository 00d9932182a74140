"""Property-based MoE executor equivalence (bounded ``ci`` profile).

The contract the executor API must keep:

* ``executor="grouped"`` is DROPLESS: it equals ``moe_forward_oracle``
  to 1e-5 for every routing draw — balanced, Zipf-skewed, and the
  all-tokens-to-one-expert worst case — with bit-equal token coverage
  (kept == routed, zero drop ledger);
* ``executor="dense"`` equals the oracle restricted to exactly the
  NON-DROPPED (token, k) pair set: recombining the oracle's per-pair
  expert outputs under the dense drop mask reproduces the dense output.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.config import get_arch, reduced_config
from repro.models import Model
from repro.models.mlp import mlp_forward
from repro.models.moe import (_all_experts_out, moe_forward,
                              moe_forward_oracle, route)

from conftest import tiny_model


def _moe_setup(num_experts=None, top_k=None, capacity_factor=None, seed=0):
    cfg, model = tiny_model("qwen2-moe-a2.7b")
    moe = cfg.moe
    moe = dataclasses.replace(
        moe,
        num_experts=num_experts or moe.num_experts,
        top_k=top_k or moe.top_k,
        capacity_factor=capacity_factor or moe.capacity_factor)
    cfg = dataclasses.replace(cfg, moe=moe)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))
    moe_p = jax.tree.map(lambda a: a[0], params["blocks"]["pos0"])["moe"]
    return cfg, moe_p


def _skew_router(moe_p, alpha, seed):
    """Bias router logits with a Zipf(alpha) per-expert offset so the
    routing distribution is heavily skewed (hot experts overflow any
    capacity)."""
    E = moe_p["router"].shape[-1]
    rng = np.random.default_rng(seed)
    zipf = (1.0 / np.arange(1, E + 1)) ** alpha
    bias = 4.0 * np.log(rng.permutation(zipf / zipf.max()) + 1e-9)
    p = dict(moe_p)
    p["router"] = moe_p["router"] + jnp.asarray(bias, jnp.float32)[None, :]
    return p


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 40), e=st.sampled_from([4, 8, 16]),
       k=st.integers(1, 4), alpha=st.sampled_from([0.0, 0.8, 1.2, 2.0]),
       seed=st.integers(0, 1000))
def test_grouped_matches_oracle_for_all_draws(n, e, k, alpha, seed):
    k = min(k, e)
    cfg, moe_p = _moe_setup(num_experts=e, top_k=k, capacity_factor=1.0)
    moe_p = _skew_router(moe_p, alpha, seed)
    x = (0.3 * jax.random.normal(jax.random.PRNGKey(seed),
                                 (1, n, cfg.d_model)))
    y, aux = moe_forward(moe_p, cfg, x, executor="grouped")
    y_ref = moe_forward_oracle(moe_p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    s = aux["routing"]
    np.testing.assert_array_equal(np.asarray(s.kept_counts),
                                  np.asarray(s.expert_counts))
    assert int(np.asarray(s.dropped).sum()) == 0
    assert int(np.asarray(s.expert_counts).sum()) == n * k


def test_grouped_is_dropless_where_dense_provably_drops():
    """ACCEPTANCE: under a Zipf(1.2) routing draw that overflows the
    dense capacity (nonzero drop ledger), grouped keeps bit-equal token
    coverage with the oracle and matches its output to 1e-5."""
    cfg, moe_p = _moe_setup(num_experts=8, top_k=2, capacity_factor=1.0)
    moe_p = _skew_router(moe_p, 1.2, seed=3)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (2, 48, cfg.d_model))

    y_dense, aux_dense = moe_forward(moe_p, cfg, x, executor="dense")
    dense_s = aux_dense["routing"]
    assert int(np.asarray(dense_s.dropped).sum()) > 0, \
        "setup must provoke dense drops"

    y_grouped, aux_g = moe_forward(moe_p, cfg, x, executor="grouped")
    y_oracle = moe_forward_oracle(moe_p, cfg, x)
    np.testing.assert_allclose(np.asarray(y_grouped), np.asarray(y_oracle),
                               rtol=1e-5, atol=1e-5)
    g = aux_g["routing"]
    # bit-equal coverage: every routed pair computed, none dropped
    np.testing.assert_array_equal(np.asarray(g.kept_counts),
                                  np.asarray(g.expert_counts))
    np.testing.assert_array_equal(np.asarray(g.expert_counts),
                                  np.asarray(dense_s.expert_counts))
    assert not np.asarray(g.drop_mask).any()
    # and the dense path really did compute strictly fewer pairs
    assert (np.asarray(dense_s.kept_counts).sum()
            < np.asarray(g.kept_counts).sum())


def test_all_tokens_to_one_expert():
    """Worst-case skew: a router rigged so EVERY pair lands on expert 0.
    Dense keeps only `capacity` pairs; grouped keeps all and still
    matches the oracle."""
    cfg, moe_p = _moe_setup(num_experts=8, top_k=2, capacity_factor=1.0)
    E = moe_p["router"].shape[-1]
    # router reads only feature 0, which is strictly positive for every
    # token, so logits order is fixed: expert 0 > expert 1 > all others
    w = np.zeros(moe_p["router"].shape, np.float32)
    w[0, :] = -10.0
    w[0, 0], w[0, 1] = 2.0, 1.0         # top-2 always experts {0, 1}
    p = dict(moe_p)
    p["router"] = jnp.asarray(w)
    key0, key1 = jax.random.split(jax.random.PRNGKey(0))
    x = 0.3 * jax.random.normal(key0, (1, 64, cfg.d_model))
    x = x.at[..., 0].set(jax.random.uniform(key1, (1, 64),
                                            minval=0.5, maxval=1.5))

    y_g, aux_g = moe_forward(p, cfg, x, executor="grouped")
    y_o = moe_forward_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_o),
                               rtol=1e-5, atol=1e-5)
    counts = np.asarray(aux_g["routing"].expert_counts)
    assert counts[0] == 64 and counts[1] == 64 and counts[2:].sum() == 0

    _, aux_d = moe_forward(p, cfg, x, executor="dense")
    d = aux_d["routing"]
    assert int(np.asarray(d.dropped).sum()) == 2 * 64 - 2 * int(d.capacity)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(4, 40), alpha=st.sampled_from([0.8, 1.2, 2.0]),
       seed=st.integers(0, 1000))
def test_dense_matches_oracle_on_non_dropped_pairs(n, alpha, seed):
    """Dense == oracle recombined over exactly the kept pair set."""
    cfg, moe_p = _moe_setup(num_experts=8, top_k=2, capacity_factor=1.0)
    moe_p = _skew_router(moe_p, alpha, seed)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(seed),
                                (1, n, cfg.d_model))
    y_dense, aux = moe_forward(moe_p, cfg, x, executor="dense")
    s = aux["routing"]

    m = cfg.moe
    x_flat = x.reshape(n, cfg.d_model)
    r = route(moe_p["router"], x_flat, m, valid_experts=m.num_experts)
    all_out = _all_experts_out(moe_p, cfg.activation, x_flat)   # (E, N, d)
    sel = jnp.take_along_axis(jnp.moveaxis(all_out, 0, 1),
                              r.topk_idx[..., None], axis=1)    # (N, k, d)
    w = jnp.where(jnp.asarray(s.drop_mask), 0.0, r.topk_weight)
    y_manual = jnp.einsum("nkd,nk->nd", sel, w)
    if m.num_shared_experts > 0:
        # shared experts see every token, dropped or not
        y_manual = y_manual + mlp_forward(moe_p["shared"], x_flat,
                                          cfg.activation)
    y_manual = y_manual.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_manual),
                               rtol=2e-5, atol=2e-5)
    # drop ledger consistency: mask counts == per-expert dropped counts
    dropped_pairs = np.asarray(s.drop_mask).sum()
    assert dropped_pairs == np.asarray(s.dropped).sum()


@pytest.mark.parametrize("executor", ["dense", "grouped", "oracle"])
def test_every_executor_reports_identical_routing_counts(executor):
    """expert_counts (the planner's demand signal) must be executor
    independent — the same router, the same histogram."""
    cfg, moe_p = _moe_setup(num_experts=8, top_k=2, capacity_factor=1.0)
    moe_p = _skew_router(moe_p, 1.2, seed=11)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    _, aux = moe_forward(moe_p, cfg, x, executor=executor)
    _, aux_ref = moe_forward(moe_p, cfg, x, executor="oracle")
    np.testing.assert_array_equal(np.asarray(aux["expert_counts"]),
                                  np.asarray(aux_ref["expert_counts"]))


def test_unknown_executor_rejected():
    cfg, moe_p = _moe_setup()
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="unknown MoE executor"):
        moe_forward(moe_p, cfg, x, executor="sparse")


# ---------------------------------------------------------------------------
# Fused routing: one kernel pass must equal the separate-pass reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", ["dense", "grouped", "oracle"])
def test_fused_routing_bit_equal_to_reference(executor):
    """``router_impl="fused"`` (single-pass routing with one-hot cumsum
    ranks) must be BIT-EQUAL to the separate top_k/argsort/cumsum
    reference for every executor — outputs, losses, and counts."""
    cfg, moe_p = _moe_setup(num_experts=8, top_k=2, capacity_factor=1.1)
    moe_p = _skew_router(moe_p, 1.2, seed=5)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (2, 17, cfg.d_model))
    y_ref, aux_ref = moe_forward(moe_p, cfg, x, executor=executor,
                                 router_impl="reference")
    y_fus, aux_fus = moe_forward(moe_p, cfg, x, executor=executor,
                                 router_impl="fused")
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_fus))
    for key in ("lb_loss", "z_loss", "expert_counts"):
        np.testing.assert_array_equal(np.asarray(aux_ref[key]),
                                      np.asarray(aux_fus[key]))


@pytest.mark.parametrize("executor", ["dense", "grouped"])
def test_pallas_routing_matches_reference(executor):
    """``router_impl="pallas"`` (the fused Pallas kernel feeding the
    same dispatch builders) must agree with the reference executor
    output within kernel tolerance, with identical routing decisions."""
    cfg, moe_p = _moe_setup(num_experts=8, top_k=2, capacity_factor=1.1)
    moe_p = _skew_router(moe_p, 1.2, seed=7)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (2, 13, cfg.d_model))
    y_ref, aux_ref = moe_forward(moe_p, cfg, x, executor=executor,
                                 router_impl="reference", capture=True)
    y_pal, aux_pal = moe_forward(moe_p, cfg, x, executor=executor,
                                 router_impl="pallas", capture=True)
    np.testing.assert_array_equal(np.asarray(aux_ref["expert_counts"]),
                                  np.asarray(aux_pal["expert_counts"]))
    np.testing.assert_array_equal(np.asarray(aux_ref["topk_idx"]),
                                  np.asarray(aux_pal["topk_idx"]))
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal),
                               rtol=1e-4, atol=1e-5)


def test_unknown_router_impl_rejected():
    cfg, moe_p = _moe_setup()
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="router impl"):
        moe_forward(moe_p, cfg, x, router_impl="fast")