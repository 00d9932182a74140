"""Random weights for the program under test, made by the benchmark.

The benchmark, not the program, decides the weights, so that the float32
reference (``chipbench.reference``) and the program read the same
numbers and the reference takes nothing the program computed. The tree
layout and shapes are the program's serving parameter layout; the values
come from ``--seed`` alone, drawn on the device in one jitted call in the
type the model is served in.

Distributions, by leaf name: embeddings and position tables
N(0, 0.02^2); norm scales 1 + N(0, 0.1^2) and norm biases N(0, 0.1^2),
so that a norm applied wrongly shows; every other matrix (attention,
router, experts, LM head) fan-in-scaled normal, std 1/sqrt(fan_in),
truncated at 3 std.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp


def _leaf(key, path, sds):
    name = str(getattr(path[-1], "key", path[-1]))
    shape = sds.shape
    if name in ("embed", "pos_table", "lm_head"):
        x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    elif name == "scale":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "bias":
        x = 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        x = fan_in ** -0.5 * jax.random.truncated_normal(
            key, -3.0, 3.0, shape, jnp.float32)
    return x.astype(sds.dtype)


def make(shapes, seed: int):
    """Weights for the tree of ``jax.ShapeDtypeStruct`` ``shapes`` from
    ``seed`` (any non-negative integer), on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [_leaf(k, p, s) for k, (p, s) in zip(keys, leaves)])

    # fold the seed in 31-bit pieces: seeds may exceed 32 bits
    key = jax.random.PRNGKey(0)
    s = int(seed)
    while True:
        key = jax.random.fold_in(key, s & 0x7FFFFFFF)
        s >>= 31
        if not s:
            break
    return build(key)
