"""A test fixture's layers, for the float32 reference: what only a
reference module's own ``layers`` can describe.

A pattern of two layers, repeated: grouped-query attention (rotate-half
rotary positions) with a dense SwiGLU feed-forward, then grouped-query
attention with a routed MoE layer (softmax router, top-k renormalized)
and one shared SwiGLU expert that every token passes through, added to
the routed experts' output. Pre-RMSNorm, the LM head tied to the token
embedding. The dense layer is common's default; the MoE layer is this
module's.
"""
from functools import partial

import jax

from chipbench.reference import common
from chipbench.reference.common import Arch, Layer


def arch(cfg) -> Arch:
    m = cfg["model"]
    return Arch(num_layers=m["num_layers"], d_model=m["d_model"],
                num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
                head_dim=m["head_dim"], num_experts=m["num_experts"],
                top_k=m["top_k"], vocab_size=m["vocab_size"], norm="rmsnorm",
                norm_eps=m["norm_eps"], pos="rope",
                rope_theta=m["rope_theta"], act="swiglu", tied=True)


def _moe(a: Arch, p, h2, logits, chosen, mode):
    """Routed experts under ``chosen`` plus the shared expert."""
    return (common.routed(a, p, h2, logits, chosen, mode)
            + common.dense_ffn(a, p["shared"], h2, mode))


@partial(jax.jit, static_argnames=("a", "mode"))
def full(a: Arch, lp, x, *, mode):
    lp = common.f32(lp)
    u, kv = common.attend_sequence(a, lp, x, mode)
    h2 = common.norm(a, lp["norm2"], u)
    logits = common.mm(h2, lp["moe"]["router"], mode)
    y = _moe(a, lp["moe"], h2, logits, common.own_choice(a, logits), mode)
    return u + y, kv, u, logits


@partial(jax.jit, static_argnames=("a",))
def attend(a: Arch, lp, h, t, kv):
    lp = common.f32(lp)
    u = common.attend_paths(a, lp, h, t, kv)
    return u, common.mm(common.norm(a, lp["norm2"], u), lp["moe"]["router"],
                        "f32")


@partial(jax.jit, static_argnames=("a",))
def ffn(a: Arch, lp, u, logits, chosen):
    lp = common.f32(lp)
    h2 = common.norm(a, lp["norm2"], u)
    return u + _moe(a, lp["moe"], h2, logits, chosen, "f32")


def layers(cfg, params):
    a = arch(cfg)
    out = []
    for j in range(a.num_layers // 2):
        out.append(common.default_layer(a, common.scanned(params, "pos0", j)))
        out.append(Layer(
            weights=common.scanned(params, "pos1", j),
            full=lambda lp, x, mode: full(a, lp, x, mode=mode),
            attend=lambda lp, h, t, kv: attend(a, lp, h, t, kv),
            ffn=lambda lp, u, s, c: ffn(a, lp, u, s, c),
            top_k=a.top_k))
    return out
