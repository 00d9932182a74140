"""Launcher-layer unit tests that don't need 512 devices: input specs,
applicability matrix, sharding rules, chunked CE, microbatched train step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import SHAPES, get_arch, list_archs
from repro.configs import ASSIGNED
from repro.launch.specs import applicable
from repro.models.common import chunked_head_cross_entropy, cross_entropy

from conftest import make_inputs, tiny_model


def test_applicability_matrix():
    """DESIGN.md §8: exactly these archs run long_500k."""
    runs = {a for a in ASSIGNED
            if applicable(get_arch(a), SHAPES["long_500k"])[0]}
    assert runs == {"gemma3-12b", "llava-next-mistral-7b", "xlstm-350m",
                    "zamba2-7b"}
    for a in ASSIGNED:          # all other shapes always apply
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert applicable(get_arch(a), SHAPES[s])[0]


def test_all_40_pairs_enumerate():
    pairs = [(a, s) for a in ASSIGNED for s in SHAPES]
    assert len(pairs) == 40
    skipped = [(a, s) for a, s in pairs
               if not applicable(get_arch(a), SHAPES[s])[0]]
    assert len(skipped) == 6          # documented skips


def test_chunked_ce_matches_plain():
    key = jax.random.PRNGKey(0)
    B, S, d, V = 2, 40, 16, 50
    x = jax.random.normal(key, (B, S, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V))
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, 40)
    plain = cross_entropy((x @ w), labels, valid_vocab=40)
    chunked = chunked_head_cross_entropy(x, w, labels, valid_vocab=40,
                                         chunk=16)
    np.testing.assert_allclose(float(chunked), float(plain), rtol=1e-5)


def test_chunked_ce_gradients_match():
    B, S, d, V = 2, 24, 8, 30
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (d, V))
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
    g1 = jax.grad(lambda w: cross_entropy(x @ w, labels, valid_vocab=V))(w)
    g2 = jax.grad(lambda w: chunked_head_cross_entropy(
        x, w, labels, valid_vocab=V, chunk=8))(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-6)


def test_microbatched_train_step_matches_full():
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    cfg, model = tiny_model("codeqwen1.5-7b")
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_inputs(cfg, batch=4, seq=16)
    opt = adamw_init(params)
    full = make_train_step(model, microbatch=1)
    mb = make_train_step(model, microbatch=2)
    p1, _, m1 = jax.jit(full)(params, opt, batch)
    p2, _, m2 = jax.jit(mb)(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-5)


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    txt = """
  %all-reduce.1 = bf16[128,256]{1,0} all-reduce(%x), replica_groups=...
  %all-to-all.2 = (f32[2,8]{1,0}, /*index=1*/f32[2,8]{1,0}) all-to-all(%a, %b)
  %ag = f32[64]{0} all-gather(%y), dimensions={0}
  %other = f32[8]{0} add(%p, %q)
"""
    out, counts = collective_bytes(txt)
    assert out["all-reduce"] == 128 * 256 * 2
    assert out["all-to-all"] == 2 * 2 * 8 * 4
    assert out["all-gather"] == 64 * 4
    assert counts["collective-permute"] == 0


@pytest.mark.parametrize("name,shape,want", [
    ("gpt2-moe", "decode_32k", ("data", None, "model")),
    ("granite-34b", "decode_32k", ("data", None, None)),    # one KV head
    ("gemma3-12b", "long_500k", (None, "data", "model")),   # batch 1
    ("whisper-small", "decode_32k", ("data", None, "model")),
])
def test_decode_cache_specs_fuse_kv_heads(name, shape, want):
    """Decode input specs lay every attention cache out (nb, B, T,
    nkv*hd) and shard batch or time over data and the fused head axis
    over model only when the KV heads divide it."""
    from jax.sharding import AbstractMesh
    from repro.launch.specs import input_specs
    cfg, _ = tiny_model(name)
    specs = input_specs(cfg, SHAPES[shape],
                        AbstractMesh((2, 2), ("data", "model")))
    flat = jax.tree_util.tree_flatten_with_path(specs["cache"])[0]
    kv = [(jax.tree_util.keystr(p), s) for p, s in flat
          if "'attn'" in jax.tree_util.keystr(p)
          or "'cross'" in jax.tree_util.keystr(p)]
    assert kv
    hd = cfg.resolved_head_dim
    for path, s in kv:
        assert s.ndim == 4 and s.shape[-1] == cfg.num_kv_heads * hd, path
        assert tuple(s.sharding.spec) == (None,) + want, path


def test_dense_threshold_switches_decode_path():
    """dense_threshold above the cache length must not change results."""
    cfg, model = tiny_model("codeqwen1.5-7b")
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_inputs(cfg, batch=1, seq=8)
    _, cache = model.prefill(params, batch["tokens"])
    cache = model.prepare_decode_cache(cache, 8192)
    tok = batch["tokens"][:, -1:]
    lg1, _ = model.decode_step(params, tok, cache, jnp.int32(8))
    model.decode_dense_threshold = 1 << 30
    lg2, _ = model.decode_step(params, tok, cache, jnp.int32(8))
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               rtol=1e-4, atol=1e-5)


def test_ep_config_for_plan_maps_comm_design_to_shard_map_knobs():
    """A DeploymentPlan configures the expert-parallel realization: the
    pipeline chunk drives lax.scan beta, direct transfer brings the
    payload cap along."""
    from repro.core.costmodel import PlatformSpec
    from repro.launch.specs import ep_config_for_plan
    from repro.plan import DeploymentPlan

    def mk(methods, beta):
        L, E = len(methods), 2
        return DeploymentPlan(
            method=np.array(methods), beta=beta,
            mem_mb=np.full((L, E), 1024.0), replicas=np.ones((L, E), int),
            demand=np.zeros((L, E)), layer_cost=np.zeros(L),
            layer_latency=np.zeros(L))

    spec = PlatformSpec()
    pipelined = ep_config_for_plan(mk([1, 2, 1], beta=8), spec)
    assert pipelined == {"beta": 8, "max_chunk_bytes": None,
                         "variant": "ep_beta8"}
    direct = ep_config_for_plan(mk([3, 3], beta=1), spec)
    assert direct["beta"] == 1
    assert direct["max_chunk_bytes"] == int(spec.payload_bytes)
    assert direct["variant"] == "ep"
    storage = ep_config_for_plan(mk([2, 2], beta=1))
    assert storage == {"beta": 1, "max_chunk_bytes": None, "variant": "ep"}
    # grouped executor: same beta drives the chunks over SORTED expert
    # groups; the capacity payload cap does not apply to ragged payloads
    grouped = ep_config_for_plan(mk([1, 3, 1], beta=4), spec,
                                 executor="grouped")
    assert grouped == {"beta": 4, "max_chunk_bytes": None,
                       "variant": "ep_grouped_beta4",
                       "executor": "grouped"}
