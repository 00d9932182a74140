"""Pallas TPU kernels for the MoE-serving compute hot spots.

Each kernel package ships three modules:
* ``kernel.py`` -- pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
* ``ops.py``    -- jit'd public wrapper (compiled on a TPU, interpreted
                    elsewhere: ``repro.device.interpret_kernels``)
* ``ref.py``    -- pure-jnp oracle used by the allclose tests

Kernels:
* ``expert_ffn``       -- blocked expert SwiGLU/GELU matmul over dense
                          (E, C, d) capacity buffers (drops overflow)
* ``grouped_moe``      -- DROPLESS ragged grouped GEMM over expert-sorted
                          block-aligned groups (scalar-prefetched
                          tile->expert indirection; cost ∝ routed tokens)
* ``router_topk``      -- fused router matmul + softmax + top-k
* ``decode_attention`` -- GQA flash-decode over a KV cache (online softmax,
                          sliding-window masking)
"""
from repro.kernels.expert_ffn.ops import expert_ffn_pallas  # noqa: F401
from repro.kernels.grouped_moe.ops import grouped_moe_pallas  # noqa: F401
from repro.kernels.router_topk.ops import router_topk_pallas  # noqa: F401
from repro.kernels.decode_attention.ops import (  # noqa: F401
    decode_attention_pallas)
