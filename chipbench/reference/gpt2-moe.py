"""gpt2-moe as the program serves it, for the float32 reference.

GPT-2's 12-layer decoder (learned positions, pre-LayerNorm, GELU in its
tanh form) with each MLP turned into a 4-expert top-1 MoE layer: router
softmax over the experts, top-1, weight renormalized (so 1), expert
``gelu(x W_in) W_out``, and the LM head tied to the token embedding.
Departures from GPT-2 that the program makes and the reference follows:
no bias on any linear layer, LayerNorm epsilon 1e-6 (GPT-2: 1e-5).
"""
from chipbench.reference.common import Arch


def arch(cfg) -> Arch:
    m = cfg["model"]
    return Arch(num_layers=m["num_layers"], d_model=m["d_model"],
                num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
                head_dim=m["head_dim"], num_experts=m["num_experts"],
                top_k=m["top_k"],
                vocab_size=m["vocab_size"], norm="layernorm",
                norm_eps=m["norm_eps"], pos="learned", rope_theta=0.0,
                act="gelu", tied=m["tied"])
