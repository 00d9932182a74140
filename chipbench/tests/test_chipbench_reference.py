"""The float32 reference against the program's own ``Model.forward`` at a
tiny size on the CPU, for each configuration's equations."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import common
from chipbench_tiny import config

REF = Path(common.__file__).parent
NAMES = ["gpt2-moe", "granite-moe-3b-a800m"]


def reference_arch(cfg):
    spec = importlib.util.spec_from_file_location(
        "ref_" + cfg["name"].replace("-", "_"), REF / f"{cfg['name']}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.arch(cfg)


def setup(name, seed=3):
    from repro.models import Model

    cfg, mc = config(name)
    model = Model(mc)
    shapes = jax.eval_shape(lambda k: model.init_params(k), jax.random.PRNGKey(0))
    params = weights.make(shapes, seed)      # float32 here: same numbers
    return cfg, model, params, reference_arch(cfg)


@pytest.mark.parametrize("name", NAMES)
def test_reference_matches_model_forward(name):
    cfg, model, params, arch = setup(name)
    toks = np.random.default_rng(0).integers(0, arch.vocab_size, 40)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.forward(params, jnp.asarray(toks[None]),
                                        moe_executor="grouped")[0][0])
    want = want[:, : arch.vocab_size]
    rows, row_pos = common.admissible_rows(arch, params, toks, 0,
                                           near_tie=0.0, pad_to=64)
    got = np.asarray(rows)
    assert got.shape == want.shape and np.array_equal(row_pos, np.arange(40))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("name", NAMES)
def test_served_gap_is_zero_for_the_reference_argmax(name):
    cfg, model, params, arch = setup(name, seed=4)
    toks = np.random.default_rng(1).integers(0, arch.vocab_size, 30)
    rows, row_pos = common.admissible_rows(arch, params, toks, 10,
                                           near_tie=0.3, pad_to=64)
    best = np.asarray(rows[:20]).argmax(-1)
    assert np.all(common.served_gaps(rows, row_pos, best) == 0.0)
    worst = np.asarray(rows[:20]).argmin(-1)
    assert np.all(common.served_gaps(rows, row_pos, worst) > 1.0)


@pytest.mark.parametrize("name", NAMES)
def test_a_path_without_a_flip_reproduces_the_base_row(name):
    """With no near ties admitted the paths add nothing; with every
    decision admitted, each base row still has a path-free gap of 0."""
    cfg, model, params, arch = setup(name, seed=5)
    toks = np.random.default_rng(2).integers(0, arch.vocab_size, 24)
    r0, p0 = common.admissible_rows(arch, params, toks, 4, near_tie=0.0,
                                    pad_to=64)
    r1, p1 = common.admissible_rows(arch, params, toks, 4, near_tie=100.0,
                                    max_flips=1, pad_to=64)
    assert len(p0) == 20 and len(p1) > 20
    np.testing.assert_allclose(np.asarray(r1[:20]), np.asarray(r0), rtol=0,
                               atol=1e-5)
