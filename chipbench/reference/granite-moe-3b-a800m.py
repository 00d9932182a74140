"""granite-moe-3b-a800m as the program serves it, for the float32
reference.

32 pre-RMSNorm decoder layers; grouped-query attention (24 query heads,
8 key/value heads of 64) with rotate-half rotary positions, theta 10000;
a 40-expert MoE layer: router softmax over the experts, top-8, weights
renormalized over the eight, SwiGLU experts
``(silu(x W_gate) * x W_up) W_down`` of width 512; the LM head tied to the
token embedding. Departures from the published model that the program
makes and the reference follows: no embedding, attention, residual or
logit multipliers (published: 12, 1/64, 0.22, 1/6), so attention scores
are scaled by 1/sqrt(64).
"""
from chipbench.reference.common import Arch


def arch(cfg) -> Arch:
    m = cfg["model"]
    return Arch(num_layers=m["num_layers"], d_model=m["d_model"],
                num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
                head_dim=m["head_dim"], num_experts=m["num_experts"],
                top_k=m["top_k"],
                vocab_size=m["vocab_size"], norm="rmsnorm",
                norm_eps=m["norm_eps"], pos="rope",
                rope_theta=m["rope_theta"], act="swiglu", tied=True)
