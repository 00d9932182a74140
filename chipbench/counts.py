"""Operations and bytes the algorithm needs, from a configuration's shapes.

These count what serving a token requires, not what today's
implementation happens to read, so that a later change that reads less
moves the measured time and not the count:

* weights are read once per step, in the model's dtype (2 bytes for
  bfloat16): attention and router weights of every layer, the experts
  that the step's tokens touch, and the LM head;
* the keys and values of each live request at its own valid length,
  in the model's dtype;
* a multiply-add is two operations; a token's operations are twice its
  active parameters plus attention's QK and PV against its context.

``m`` is the ``model`` object of a configuration file.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_params(m: Dict) -> int:
    d, nh, nkv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d


def expert_params(m: Dict) -> int:
    mats = 3 if m["act"] == "swiglu" else 2
    return mats * m["d_model"] * m["d_expert_ff"]


def active_params(m: Dict) -> int:
    """Parameters one token multiplies by, LM head included."""
    per_layer = (attn_params(m) + m["d_model"] * m["num_experts"]
                 + m["top_k"] * expert_params(m))
    return m["num_layers"] * per_layer + m["d_model"] * m["vocab_size"]


def attention_flops(m: Dict, ctx: int) -> int:
    """QK and PV for one query against ``ctx`` keys, over all layers."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * ctx


def token_flops(m: Dict, ctx: int) -> int:
    return 2 * active_params(m) + attention_flops(m, ctx)


def kv_bytes(m: Dict, rows: int) -> int:
    b = DTYPE_BYTES[m["dtype"]]
    return 2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * rows * b


def decode_step(m: Dict, lengths: Sequence[int],
                touched: Optional[Sequence[int]] = None) -> Dict[str, float]:
    """One batched decode step: one token per live request.

    ``lengths``: each live request's valid cache rows after the step
    (context including the new token); ``touched``: per layer, the
    experts the step's tokens were routed to (all, if not given)."""
    b = DTYPE_BYTES[m["dtype"]]
    L, E = m["num_layers"], m["num_experts"]
    flops = sum(token_flops(m, n) for n in lengths)
    n_exp = sum(touched) if touched is not None else L * E
    weights = (L * (attn_params(m) + m["d_model"] * E)
               + n_exp * expert_params(m)
               + m["d_model"] * m["vocab_size"])
    byt = b * weights + sum(kv_bytes(m, n) for n in lengths)
    return {"flops": float(flops), "bytes": float(byt)}


def prefill(m: Dict, n: int) -> Dict[str, float]:
    """A prompt of ``n`` tokens, causal: token i attends to i + 1 keys."""
    b = DTYPE_BYTES[m["dtype"]]
    L, E = m["num_layers"], m["num_experts"]
    flops = 2 * active_params(m) * n + attention_flops(m, n * (n + 1) // 2)
    n_exp = L * min(E, n * m["top_k"])
    weights = (L * (attn_params(m) + m["d_model"] * E)
               + n_exp * expert_params(m) + m["d_model"] * m["vocab_size"])
    return {"flops": float(flops), "bytes": float(b * weights
                                                  + kv_bytes(m, n))}


def least_seconds(c: Dict[str, float], peak: Dict) -> Dict[str, float]:
    """Roofline: the larger of operations over peak FLOP/s and bytes
    over peak bandwidth, and which of the two bounds it."""
    tf = c["flops"] / peak["flops_per_s"]
    tb = c["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(tf, tb), "bound": "flops" if tf >= tb else "bytes"}
