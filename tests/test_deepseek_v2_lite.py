"""DeepSeek-V2-Lite's mechanisms at a small size on the CPU: latent
attention (published prefill, absorbed decode through the slot cache),
YaRN rotary frequencies and softmax scale, unrenormalized top-k routing,
and a layer that holds a share of the experts."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_arch
from repro.models import attention, blocks, moe
from repro.models.common import rope_tables, yarn_frequencies, yarn_mscale
from repro.models.mlp import mlp_forward
from repro.serving import ServingEngine
from repro.serving.kv_slots import SlotKVCache

from conftest import tiny_model

# Absorbed decode against the published full forward, float32 weights:
# the two differ only in the order of float32 sums (the key and value
# decompression folded into the query and the output), about 1e-6 of
# logits of size 1 through three layers; 5e-5 leaves room for that and
# is far below bf16's rounding (2**-9 of each latent value), which a
# latent cache or an absorbed product in bf16 would add.
ABSORBED_TOL = 5e-5


def _model():
    cfg, model = tiny_model("deepseek-v2-lite", capacity_factor=8.0)
    return cfg, model, model.init_params(jax.random.PRNGKey(3))


def _absorbed_error(cache_dtype=jnp.float32) -> float:
    """Largest logit difference of prompts prefilled into slots of the
    slot cache and decoded teacher-forced (per-slot positions), against
    the full forward of the same sequences."""
    cfg, model, params = _model()
    S, P = 12, 5
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, S), 0,
                              cfg.vocab_size)
    full, _, _ = model.forward(params, toks)
    kv = SlotKVCache(model, 3, 32)
    kv.cache = model.init_cache(3, 32, dtype=cache_dtype)
    slots = (2, 0)
    for row, slot in enumerate(slots):
        _, c = model.prefill(params, toks[row:row + 1, :P])
        kv.insert(model.prepare_decode_cache(c, 32), slot, length=P)
    err = 0.0
    for t in range(P, S):
        tok = np.zeros((3, 1), np.int32)
        pos = np.zeros(3, np.int32)
        for row, slot in enumerate(slots):
            tok[slot, 0], pos[slot] = int(toks[row, t]), t
        lg, cache = model.decode_step(params, jnp.asarray(tok), kv.cache,
                                      jnp.asarray(pos), kv_len=16)
        kv.update(cache)
        for row, slot in enumerate(slots):
            err = max(err, float(jnp.abs(lg[slot, 0] - full[row, t]).max()))
    return err


def test_absorbed_decode_through_the_slot_cache_matches_the_forward():
    assert _absorbed_error() < ABSORBED_TOL


def test_a_bf16_latent_cache_fails_the_tolerance():
    assert _absorbed_error(jnp.bfloat16) > ABSORBED_TOL


def test_bf16_absorbed_products_fail_the_tolerance(monkeypatch):
    """The decode step's absorbed ``W_UK``/``W_UV`` products fed bf16
    weights (the forward keeps float32 ones)."""
    step = blocks.mla_decode_step

    def bf16_absorbed(params, *args, **kw):
        w = params["wkv_b"].astype(jnp.bfloat16).astype(jnp.float32)
        return step({**params, "wkv_b": w}, *args, **kw)

    monkeypatch.setattr(blocks, "mla_decode_step", bf16_absorbed)
    assert _absorbed_error() > ABSORBED_TOL


def test_the_latent_cache_holds_c_and_the_rotated_key():
    """A prefill's cache is the normalized latent and the shared rope key
    after its rotation, one row a token, the leading dense layer's first
    in the stacks of every latent layer."""
    cfg, model, params = _model()
    toks = jnp.arange(7)[None] % cfg.vocab_size
    _, cache = model.prefill(params, toks)
    a = cfg.mla
    lat = cache["pos0"]["attn"]
    assert set(cache) == {"pos0"}
    assert lat["c"].shape == (cfg.num_layers, 1, 7, a.kv_lora_rank)
    assert lat["k_pe"].shape == (cfg.num_layers, 1, 7, a.qk_rope_head_dim)
    # the leading layer's rope key, recomputed by hand from its input
    lp = jax.tree.map(lambda t: t[0], params["lead"])
    from repro.models.common import apply_norm, apply_rope
    h = apply_norm(cfg.norm, lp["norm1"], params["embed"][toks])
    raw = (h @ lp["attn"]["wkv_a"])[..., a.kv_lora_rank:]
    inv, ms = rope_tables(a.qk_rope_head_dim, cfg.rope_theta,
                          cfg.rope_scaling)
    want = apply_rope(raw[:, :, None], jnp.arange(7), inv, ms)[:, :, 0]
    np.testing.assert_allclose(lat["k_pe"][0], want, atol=1e-6)


def test_blocked_prefill_matches_the_dense_one():
    """Prompts past ``flash_threshold`` take the blocked attention path;
    it gives what the dense path gives."""
    cfg, model, params = _model()
    lp = jax.tree.map(lambda t: t[0], params["lead"])["attn"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 12, cfg.d_model))
    dense = attention.mla_forward(lp, cfg, x, positions=jnp.arange(12))
    blocked = attention.mla_forward(lp, cfg, x, positions=jnp.arange(12),
                                    flash_threshold=4)
    np.testing.assert_allclose(blocked[0], dense[0], atol=1e-5)


def test_yarn_by_hand():
    """DeepSeek-V2-Lite's rope: 64 dims, theta 1e4, factor 40 over 4096,
    beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707."""
    s = get_arch("deepseek-v2-lite").rope_scaling
    dim, theta = 64, 1e4
    # correction range: dims whose wavelength makes beta rotations over
    # the original 4096 positions, floor and ceil
    lo = dim * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(theta))
    hi = dim * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(lo), math.ceil(hi)) == (10, 23)
    want = []
    for i in range(dim // 2):
        extra = theta ** (-2 * i / dim)
        ramp = min(max((i - 10) / (23 - 10), 0.0), 1.0)
        want.append(extra / 40 * ramp + extra * (1 - ramp))
    np.testing.assert_allclose(yarn_frequencies(dim, theta, s), want,
                               rtol=1e-6)
    # the softmax scale: 1/sqrt(128 + 64) times (0.1·0.707·ln 40 + 1)^2
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(yarn_mscale(40, 0.707) - m) < 1e-12
    assert abs(m - 1.2608038) < 1e-7
    cfg = get_arch("deepseek-v2-lite")
    assert abs(attention.mla_softmax_scale(cfg) - m * m / math.sqrt(192)) \
        < 1e-12
    # mscale equal on both sides: cos and sin are not scaled
    assert rope_tables(dim, theta, s)[1] == 1.0


def _routing_cfg(**moe_kw):
    cfg = get_arch("deepseek-v2-lite")
    return dataclasses.replace(cfg.moe, **moe_kw)


def test_norm_topk_prob_off_gives_the_raw_softmax():
    x = jax.random.normal(jax.random.PRNGKey(0), (10, 32))
    w = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    probs = jax.nn.softmax(x @ w, -1)
    raw_w, raw_i = jax.lax.top_k(probs, 6)
    m = _routing_cfg(norm_topk_prob=False)
    for r in (moe.route(w, x, m), moe.route_fused(w, x, m)):
        np.testing.assert_array_equal(r.topk_weight, raw_w)
        np.testing.assert_array_equal(r.topk_idx, raw_i)
    # the default stays as it was: renormalized over the k, bit for bit
    m = _routing_cfg(norm_topk_prob=True)
    old = raw_w / jnp.maximum(raw_w.sum(-1, keepdims=True), 1e-9)
    for r in (moe.route(w, x, m), moe.route_fused(w, x, m)):
        np.testing.assert_array_equal(r.topk_weight, old)
    # the Pallas router renormalizes, so it refuses the flag
    with pytest.raises(ValueError, match="norm_topk_prob"):
        moe.route_fused_pallas(w, x, _routing_cfg(norm_topk_prob=False))


@pytest.mark.parametrize("executor", ["grouped", "dense", "oracle"])
def test_four_held_shares_add_up_to_the_uncut_layer(executor):
    """Four chips' shares of 16 of the 64 experts, the shared experts
    counted once, give the uncut layer (guide's share test)."""
    base = get_arch("deepseek-v2-lite")
    cut = dataclasses.replace(
        base, d_model=32, moe=dataclasses.replace(
            base.moe, d_expert_ff=16, d_shared_ff=16, capacity_factor=64.0))
    whole = dataclasses.replace(cut, moe=dataclasses.replace(
        cut.moe, experts_held=0))
    p = moe.init_moe(jax.random.PRNGKey(5), whole)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 32))
    y_whole, _ = moe.moe_forward(p, whole, x, executor=executor)
    held = cut.moe.held
    assert held == 16
    shares = []
    for s in range(4):
        # chip s's experts are 16s .. 16s+15: its router lists them first
        ps = {k: (v[held * s:held * (s + 1)] if k.startswith("w_") else v)
              for k, v in p.items()}
        ps["router"] = jnp.roll(p["router"], -held * s, axis=1)
        y, aux = moe.moe_forward(ps, cut, x, executor=executor)
        assert aux["expert_counts"].shape == (held,)
        shares.append(y)
    shared = mlp_forward(p["shared"], x, "swiglu")
    np.testing.assert_allclose(sum(shares) - 3 * shared, y_whole,
                               atol=2e-5)


def test_rows_past_the_held_groups_are_never_read(monkeypatch):
    """A TPU's ragged_dot leaves the rows past the held experts' groups
    unwritten; the layer's output must not depend on what they hold."""
    cfg = get_arch("deepseek-v2-lite")
    cfg = dataclasses.replace(cfg, d_model=32, moe=dataclasses.replace(
        cfg.moe, d_expert_ff=16, d_shared_ff=16))
    p = moe.init_moe(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 32))
    want, _ = moe.moe_forward(p, cfg, x, executor="grouped")
    inner = moe.ragged_expert_ffn

    def unwritten(params, buf, group_sizes, activation):
        out = inner(params, buf, group_sizes, activation)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < group_sizes.sum(), out, jnp.nan)

    monkeypatch.setattr(moe, "ragged_expert_ffn", unwritten)
    got, _ = moe.moe_forward(p, cfg, x, executor="grouped")
    np.testing.assert_array_equal(got, want)


def test_a_decode_step_touches_no_more_experts_than_are_held():
    """Telemetry has a column per held expert; picks of the others count
    in ``routed_away``; the leading dense layer routes nothing."""
    cfg, model = tiny_model("deepseek-v2-lite", max_experts=8)
    held = cfg.moe.held
    assert (cfg.moe.num_experts, held, cfg.first_dense_layers) == (8, 2, 1)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, max_len=48, batch_size=3)
    rng = np.random.default_rng(0)
    for n in (5, 9, 13, 7):
        eng.submit(rng.integers(0, cfg.vocab_size, n), max_new_tokens=6)
    tel = eng.telemetry
    assert tel.demand.shape == (cfg.num_layers, held)
    steps = 0
    before = tel.demand.copy()
    while eng.step():
        steps += 1
        touched = ((tel.demand - before) > 0).sum(1)
        assert touched.max() <= held
        before = tel.demand.copy()
    assert steps > 0
    assert not tel.demand[0].any()
    routed = tel.demand.sum() + tel.routed_away
    assert routed == (tel.prefill_tokens + tel.decode_tokens) \
        * cfg.moe.top_k * cfg.num_blocks
    assert 0 < tel.routed_away < routed
    # the latent rows the steps read count as the cache's rows
    assert eng.kv_rows_read >= eng.kv_rows_live > 0
