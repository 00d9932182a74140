"""The serving path's Pallas kernels, and its decode step, compile for a
TPU v5e.

Each case lowers a kernel wrapper with ``interpret=False`` against a
described (not attached) ``v5e:2x2`` topology, at gpt2-moe's published
widths (d 768, ff 3072, 4 experts, top-1, bf16) and the sizes
``chip_smoke.py`` serves (8 decode slots, 1024 KV rows, prompts of tens
to low hundreds of tokens). Mosaic refuses here what interpret mode
accepts: unaligned blocks, unsupported primitives, relayouts. Nothing
runs, so these say nothing about results or speed.

The decode-step cases compile the serving engine's whole decode program
at a benchmark cell's shapes and read XLA's buffers: the layer scan must
update the stacked KV (or latent) cache in place, so no op copies the
stack or holds one layer's cache at ``max_len``, and the weights, the
cache and the program's temporaries must fit the chip's 15.75e9 bytes.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and under several test workers only
the worker given this file may.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs  # noqa: F401  (registers the architectures)
from repro.config import get_arch
from repro.kernels.decode_attention.ops import decode_attention_pallas
from repro.kernels.expert_ffn.ops import expert_ffn_pallas
from repro.kernels.grouped_moe.ops import grouped_moe_pallas
from repro.kernels.router_topk.ops import (router_topk_fused_pallas,
                                           router_topk_pallas)
from repro.models import Model
from repro.models.moe import grouped_rows_for
from repro.serving import ServingEngine

D, FF, E, K = 768, 3072, 4, 1          # gpt2-moe
SLOTS, MAX_LEN, HEADS, HEAD_DIM = 8, 1024, 12, 64
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of any persistent cache the environment configured."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _router(n):
    return (lambda x, w: router_topk_pallas(x, w, k=K, interpret=False),
            [((n, D), BF16), ((D, E), BF16)])


def _router_fused(n):
    return (lambda x, w: router_topk_fused_pallas(x, w, k=K,
                                                  interpret=False),
            [((n, D), BF16), ((D, E), BF16)])


def _decode_attention(kv_len):
    # q in the model dtype, K/V in the slot cache's dtype (float32)
    return (lambda q, k, v, valid: decode_attention_pallas(
                q, k, v, valid, interpret=False),
            [((SLOTS, HEADS, 1, HEAD_DIM), BF16),
             ((SLOTS, kv_len, HEADS, HEAD_DIM), F32),
             ((SLOTS, kv_len, HEADS, HEAD_DIM), F32),
             ((SLOTS,), I32)])


def _grouped_moe(n_tokens):
    rows = grouped_rows_for(n_tokens * K, E)
    return (lambda x, te, wi, wo: grouped_moe_pallas(
                x, te, wi, None, wo, activation="gelu", interpret=False),
            [((rows, D), BF16), ((rows // 8,), I32),
             ((E, D, FF), BF16), ((E, FF, D), BF16)])


def _expert_ffn(capacity):
    return (lambda buf, wi, wo: expert_ffn_pallas(
                buf, wi, None, wo, activation="gelu", interpret=False),
            [((E, capacity, D), BF16), ((E, D, FF), BF16),
             ((E, FF, D), BF16)])


CASES = {
    "router_topk-decode": _router(SLOTS),
    "router_topk-prefill": _router(200),
    "router_fused-decode": _router_fused(SLOTS),
    "router_fused-prefill": _router_fused(200),
    "router_fused-widest-tile": _router_fused(512),
    "decode_attention-max_len": _decode_attention(MAX_LEN),
    "decode_attention-ragged": _decode_attention(160),
    "grouped_moe-decode": _grouped_moe(SLOTS),
    "grouped_moe-prefill": _grouped_moe(200),
    "expert_ffn": _expert_ffn(64),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel was lowered by Mosaic, not inlined as interpreted jnp
    assert "tpu_custom_call" in compiled.as_text()


# (architecture, program options, decode slots): the benchmark cells'
# engines, 1024 cache rows, a step whose longest live request reads 1008
DECODE_CELLS = {
    "gpt2-moe": ("gpt2-moe", {"tie_embeddings": True}, 64),
    "granite-moe-3b-a800m": ("granite-moe-3b-a800m", {}, 24),
    "dsv2lite-chat-backlog": ("deepseek-v2-lite", {}, 64),
}
DECODE_KV_LEN = 1008
IN_PLACE = ("scatter", "dynamic-update-slice")
OP = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
HLO_TYPES = {"float32": "f32", "bfloat16": "bf16", "int32": "s32"}


def _hlo_ops(text):
    """(name, element type, dims, opcode, fused root opcode or None) of
    every array-valued op, and no parameter, in a compiled module's
    text."""
    roots, ops, comp = {}, [], None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
        m = OP.match(line)
        if not m:
            continue
        name, dt, dims, opcode = m.groups()
        if line.lstrip().startswith("ROOT"):
            roots[comp] = opcode
        if opcode == "parameter":
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        shape = tuple(int(d) for d in filter(None, dims.split(",")))
        ops.append((name, dt, shape, opcode, called and called.group(1)))
    return [(name, dt, dims, opcode, roots.get(c) if c else None)
            for name, dt, dims, opcode, c in ops]


def _cache_copies(ops, cache, params):
    """The ops of any element type that hold one layer of a cache stack
    (by element count, so a reshaped or converted copy counts too), and
    the ops that move a whole stack other than loop plumbing and in-place
    updates. An op whose type and dims are those of a weight, or of one
    layer's slice of a stacked weight, is that weight, not the cache."""
    stack = {leaf.size for leaf in jax.tree.leaves(cache)}
    layer = {leaf.size // leaf.shape[0] for leaf in jax.tree.leaves(cache)}
    weights = {(HLO_TYPES[leaf.dtype.name], shape)
               for leaf in jax.tree.leaves(params)
               for shape in (leaf.shape, leaf.shape[1:])}
    found = []
    for name, dt, dims, opcode, root in ops:
        n = math.prod(dims)
        if (dt, dims) in weights:
            continue
        if n in layer:
            found.append(name)
        elif n in stack and not (
                opcode in ("get-tuple-element", "bitcast") + IN_PLACE
                or (opcode == "fusion" and root in IN_PLACE)):
            found.append(name)
    return found


@pytest.mark.parametrize("dtype", ["f32", "bf16", "s32"])
def test_a_layer_sized_op_of_any_type_is_a_cache_copy(dtype):
    """A one-layer copy of a float32 cache stack is caught whatever its
    element type (a convert fused into the slice is a copy too), a
    weight whose element count equals a layer's is not."""
    cache = {"k": jax.ShapeDtypeStruct((3, 2, 16, 8), F32)}
    params = {"wo": jax.ShapeDtypeStruct((3, 16, 16), BF16)}
    text = "\n".join([
        f"%fused_convert (p: f32[2,16,8]) -> {dtype}[2,16,8] {{",
        f"  ROOT %convert = {dtype}[2,16,8]{{2,1,0}} convert(%p)",
        "}",
        "ENTRY %main (a: f32[3,2,16,8], w: bf16[3,16,16]) -> f32[] {",
        "  %a = f32[3,2,16,8]{3,2,1,0} parameter(0)",
        "  %w = bf16[3,16,16]{2,1,0} parameter(1)",
        f"  %layer_copy = {dtype}[2,16,8]{{2,1,0}} fusion(%a), kind=kLoop, "
        "calls=%fused_convert",
        "  %wo_slice = bf16[16,16]{1,0} dynamic-slice(%w)",
        "  %stack_write = f32[3,2,16,8]{3,2,1,0} dynamic-update-slice(%a)",
        "  ROOT %r = f32[] constant(0)",
        "}"])
    # the fused convert and the fusion that holds it; not the weight
    assert _cache_copies(_hlo_ops(text), cache, params) == [
        "convert", "layer_copy"]


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
def test_decode_step_updates_the_cache_in_place(cell, one_chip,
                                                no_persistent_cache):
    arch, program, slots = DECODE_CELLS[cell]
    cfg = dataclasses.replace(get_arch(arch), **program)
    model = Model(cfg)
    eng = ServingEngine(model, None, max_len=16, batch_size=1)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda k: model.init_params(k, dtype=BF16), jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(slots,
                                                            MAX_LEN)))
    compiled = eng._jit_decode.lower(
        params, on_chip(jax.ShapeDtypeStruct((slots, 1), I32)), cache,
        on_chip(jax.ShapeDtypeStruct((slots,), I32)), None,
        DECODE_KV_LEN).compile()
    ops = _hlo_ops(compiled.as_text())
    assert ops
    # no layer's cache sliced out, or written back, at max_len, and the
    # stack itself only passes through the loop and is updated in place
    assert not _cache_copies(ops, cache, params)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
