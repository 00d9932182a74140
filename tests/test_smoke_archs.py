"""Per-architecture smoke tests (deliverable f).

Each assigned architecture is instantiated as a REDUCED variant of the same
family (2 unit-blocks, d_model<=512, <=4 experts) and runs one forward and
one train step on CPU, asserting output shapes and absence of NaNs.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.config import get_arch, list_archs
from repro.configs import ASSIGNED, PAPER_MODELS
from repro.optim import adamw_init, adamw_update

from conftest import forward_kwargs, make_inputs, tiny_model

ALL = list(ASSIGNED) + list(PAPER_MODELS)


@pytest.mark.parametrize("name", ALL)
def test_forward_shapes_and_finite(name):
    cfg, model = tiny_model(name)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_inputs(cfg)
    logits, aux, _ = model.forward(params, batch["tokens"],
                                   **forward_kwargs(batch))
    B, S = batch["tokens"].shape
    extra = cfg.frontend_tokens if cfg.frontend == "vision_stub" else 0
    assert logits.shape == (B, S + extra, cfg.padded_vocab)
    assert jnp.isfinite(logits).all(), f"{name}: non-finite logits"


@pytest.mark.parametrize("name", ALL)
def test_one_train_step(name):
    cfg, model = tiny_model(name)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_inputs(cfg)
    opt = adamw_init(params)

    @jax.jit
    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch)
        params, opt = adamw_update(grads, opt, params, lr=1e-3)
        return params, opt, loss

    params2, opt2, loss = step(params, opt, batch)
    assert jnp.isfinite(loss), f"{name}: non-finite loss"
    # parameters actually moved
    moved = jax.tree.map(lambda a, b: jnp.abs(a - b).max(), params, params2)
    assert max(float(x) for x in jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("name", ALL)
def test_second_step_decreases_loss_direction(name):
    """Loss is finite after two steps and gradients stay finite."""
    cfg, model = tiny_model(name)
    params = model.init_params(jax.random.PRNGKey(0))
    batch = make_inputs(cfg)
    opt = adamw_init(params)
    for _ in range(2):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        gleaves = jax.tree.leaves(grads)
        assert all(jnp.isfinite(g).all() for g in gleaves)
        params, opt = adamw_update(grads, opt, params, lr=1e-3)
    assert jnp.isfinite(loss)


def test_all_assigned_archs_registered():
    for name in ASSIGNED:
        cfg = get_arch(name)
        assert cfg.source, f"{name} missing source citation"
    assert len(ASSIGNED) == 11
    assert len(set(get_arch(a).arch_type for a in ASSIGNED)) >= 6
