"""Gold test: sequential one-token decode == full causal forward.

Covers KV-cache attention (full + sliding window), latent attention with a
leading dense layer (deepseek-v2-lite), chunked SSD (mamba2), chunkwise
mLSTM, sequential sLSTM, MoE dispatch, VLM prefix, enc-dec cross
attention — all through the public prefill/decode API.
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import forward_kwargs, make_inputs, tiny_model

CAUSAL = ["gpt2-moe", "codeqwen1.5-7b", "gemma3-12b", "xlstm-350m",
          "zamba2-7b", "qwen2-moe-a2.7b", "granite-moe-3b-a800m",
          "llava-next-mistral-7b", "granite-34b", "qwen3-4b",
          "whisper-small", "bert2bert-moe", "deepseek-v2-lite"]


@pytest.mark.parametrize("name", CAUSAL)
def test_decode_matches_forward(name):
    cfg, model = tiny_model(name, capacity_factor=8.0)
    params = model.init_params(jax.random.PRNGKey(1))
    S = 12
    batch = make_inputs(cfg, batch=2, seq=S)
    kw = forward_kwargs(batch)
    n_front = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0

    logits_full, _, _ = model.forward(params, batch["tokens"], **kw)
    _, cache = model.prefill(params, batch["tokens"][:, :1], **kw)
    cache = model.prepare_decode_cache(cache, 64)
    tol = 5e-4 if name == "xlstm-350m" else 5e-5
    for t in range(1, S):
        lg, cache = model.decode_step(params, batch["tokens"][:, t:t + 1],
                                      cache, jnp.int32(t + n_front))
        err = float(jnp.abs(lg[:, 0] - logits_full[:, n_front + t]).max())
        assert err < tol, f"{name} step {t}: err={err}"


def test_sliding_window_restricts_context():
    """Stacked window layers have receptive field L*W: logits at position t
    must not depend on tokens further back than num_layers * window."""
    cfg, model = tiny_model("llava-next-mistral-7b")
    assert cfg.sliding_window > 0
    params = model.init_params(jax.random.PRNGKey(0))
    W = cfg.sliding_window
    S = cfg.num_layers * W + 16
    key = jax.random.PRNGKey(2)
    toks = jax.random.randint(key, (1, S), 0, cfg.vocab_size)
    front = make_inputs(cfg, batch=1, seq=S)["frontend"]
    lg1, _, _ = model.forward(params, toks, frontend=front)
    # perturb a token far outside the window of the last position
    toks2 = toks.at[0, 0].set((toks[0, 0] + 1) % cfg.vocab_size)
    lg2, _, _ = model.forward(params, toks2, frontend=front)
    last = -1
    assert float(jnp.abs(lg1[0, last] - lg2[0, last]).max()) < 1e-5


@pytest.mark.parametrize("name", ["gpt2-moe", "gemma3-12b", "zamba2-7b",
                                  "whisper-small"])
def test_decode_scan_carries_self_attention_cache(name):
    """The decode layer scan carries every self-attention K/V stack (full,
    windowed, shared) and writes it in place: the stacks enter as the
    scan's carry, leave as its carry, and never appear among its scanned
    inputs or stacked outputs. Recurrent states and cross caches are
    scanned per block."""
    cfg, model = tiny_model(name)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = model.init_cache(3, 32)
    pos = jnp.array([0, 5, 9], jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, c: model.decode_step(p, jnp.zeros((3, 1), jnp.int32), c,
                                       pos))(params, cache)
    jaxpr = closed.jaxpr
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    n_const, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    carry_in = scan.invars[n_const:n_const + n_carry]
    xs = scan.invars[n_const + n_carry:]
    carry_out, ys = scan.outvars[:n_carry], scan.outvars[n_carry:]

    n_params = len(jax.tree.leaves(params))
    cache_in = jaxpr.invars[n_params:]
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(cache)[0]]
    _, new_cache = jax.tree.unflatten(
        jax.tree.structure(model.decode_step(
            params, jnp.zeros((3, 1), jnp.int32), cache, pos)),
        jaxpr.outvars)
    cache_out = jax.tree.leaves(new_cache)
    self_attn = [i for i, p in enumerate(paths) if "['attn']" in p]
    n_attn = sum(s.mixer in ("attn", "swa", "shared_attn")
                 for s in cfg.pattern)
    assert len(self_attn) == 2 * n_attn > 0
    for i, path in enumerate(paths):
        if i in self_attn:
            assert cache_in[i] in carry_in and cache_in[i] not in xs, path
            assert cache_out[i] in carry_out and cache_out[i] not in ys, path
            assert cache_in[i].aval.ndim == 4      # (nb, B, T, nkv*hd)
        else:   # scanned; a read-only cross cache is forwarded as is
            assert cache_in[i] in xs, path
            assert cache_out[i] in ys or cache_out[i] is cache_in[i], path
