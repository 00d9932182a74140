"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count what serving a token requires, not what today's
implementation happens to read, so that a later change that reads less
moves the measured time and not the count:

* weights are read once per step, in the model's dtype (2 bytes for
  bfloat16): attention and router weights of every layer, the dense and
  shared feed-forwards, the experts that the step's tokens touch, and
  the LM head;
* the cached state of each live request at its own valid length, in the
  model's dtype;
* a multiply-add is two operations; a token's operations are twice its
  active parameters plus attention's against its context.

``m`` is the ``model`` object of a configuration file. Its shape keys
(``num_layers``, ``d_model``, ``num_heads``, ``num_kv_heads``,
``head_dim``, ``d_expert_ff``, ``num_experts``, ``top_k``, ``act``,
``vocab_size``, ``dtype``) give grouped-query attention and an MoE layer
in every layer. A layer that differs states its own numbers under these
keys, each per layer:

* ``attn_params``: attention's parameters (default: q, k, v and o);
* ``cached_per_token``: cached elements per token (default: K and V,
  ``2·num_kv_heads·head_dim``);
* ``attn_flops_per_pair``: attention's operations per query-key pair
  (default: QK and PV, ``4·num_heads·head_dim``);
* ``dense_layers``, ``d_dense_ff``: layers with a dense feed-forward of
  that width in place of the MoE layer (default none);
* ``num_shared_experts``, ``d_shared_ff``: always-on experts in each MoE
  layer and the width of each (default none; width ``d_expert_ff``);
* ``experts_held``: the experts this chip holds of the router's
  ``num_experts`` (default all). A token's routed expert work on this
  chip is then its expected share, ``top_k·experts_held/num_experts``
  experts.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(m: Dict) -> int:
    if "attn_params" in m:
        return m["attn_params"]
    d, nh, nkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def ffn_params(m: Dict, width: int) -> int:
    mats = 3 if m["act"] == "swiglu" else 2
    return mats * m["d_model"] * width


def expert_params(m: Dict) -> int:
    return ffn_params(m, m["d_expert_ff"])


def moe_layers(m: Dict) -> int:
    return m["num_layers"] - m.get("dense_layers", 0)


def held(m: Dict) -> int:
    return m.get("experts_held", m["num_experts"])


def fixed_ffn_params(m: Dict) -> int:
    """Feed-forward parameters every token multiplies by, over all
    layers: the dense layers' and the MoE layers' shared experts."""
    shared = m.get("num_shared_experts", 0) * ffn_params(
        m, m.get("d_shared_ff") or m["d_expert_ff"])
    fixed = moe_layers(m) * shared
    if m.get("dense_layers", 0):
        fixed += m["dense_layers"] * ffn_params(m, m["d_dense_ff"])
    return fixed


def routed_params(m: Dict):
    """Routed expert parameters one token multiplies by on this chip, in
    one MoE layer: ``top_k`` experts, or their expected share of the
    experts held."""
    n = m["top_k"] * expert_params(m) * held(m)
    return n // m["num_experts"] if n % m["num_experts"] == 0 \
        else n / m["num_experts"]


def active_params(m: Dict):
    """Parameters one token multiplies by, LM head included."""
    per_moe = m["d_model"] * m["num_experts"] + routed_params(m)
    return (m["num_layers"] * attn_params(m) + moe_layers(m) * per_moe
            + fixed_ffn_params(m) + m["d_model"] * m["vocab_size"])


def attention_flops(m: Dict, ctx: int) -> int:
    """Attention for one query against ``ctx`` keys, over all layers."""
    pair = m.get("attn_flops_per_pair",
                 4 * m["num_heads"] * m["head_dim"])
    return m["num_layers"] * pair * ctx


def token_flops(m: Dict, ctx: int):
    return 2 * active_params(m) + attention_flops(m, ctx)


def kv_bytes(m: Dict, rows: int) -> int:
    b = DTYPE_BYTES[m["dtype"]]
    per_token = m.get("cached_per_token",
                      2 * m["num_kv_heads"] * m["head_dim"])
    return m["num_layers"] * per_token * rows * b


def step_weights(m: Dict, n_exp: int) -> int:
    """Weights one step reads: every layer's attention, router, dense and
    shared feed-forward, ``n_exp`` experts, and the LM head."""
    return (m["num_layers"] * attn_params(m)
            + moe_layers(m) * m["d_model"] * m["num_experts"]
            + fixed_ffn_params(m) + n_exp * expert_params(m)
            + m["d_model"] * m["vocab_size"])


def decode_step(m: Dict, lengths: Sequence[int],
                touched: Optional[Sequence[int]] = None) -> Dict[str, float]:
    """One batched decode step: one token per live request.

    ``lengths``: each live request's valid cache rows after the step
    (context including the new token); ``touched``: per layer, the
    experts the step's tokens were routed to on this chip (all it
    holds, if not given)."""
    b = DTYPE_BYTES[m["dtype"]]
    flops = sum(token_flops(m, n) for n in lengths)
    n_exp = sum(touched) if touched is not None else moe_layers(m) * held(m)
    byt = b * step_weights(m, n_exp) + sum(kv_bytes(m, n) for n in lengths)
    return {"flops": float(flops), "bytes": float(byt)}


def prefill(m: Dict, n: int) -> Dict[str, float]:
    """A prompt of ``n`` tokens, causal: token i attends to i + 1 keys."""
    b = DTYPE_BYTES[m["dtype"]]
    flops = 2 * active_params(m) * n + attention_flops(m, n * (n + 1) // 2)
    n_exp = moe_layers(m) * min(held(m), n * m["top_k"])
    return {"flops": float(flops),
            "bytes": float(b * step_weights(m, n_exp) + kv_bytes(m, n))}


def least_seconds(c: Dict[str, float], peak: Dict) -> Dict[str, float]:
    """Roofline: the larger of operations over peak FLOP/s and bytes
    over peak bandwidth, and which of the two bounds it."""
    tf = c["flops"] / peak["flops_per_s"]
    tb = c["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(tf, tb), "bound": "flops" if tf >= tb else "bytes"}
