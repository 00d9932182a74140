"""DeepSeek-V2-Lite as the program serves it, for the float32 reference.

27 pre-RMSNorm decoder layers (epsilon 1e-6), each with multi-head
latent attention, in its published, unabsorbed form (no query LoRA):

* ``q = h W_q`` per head ``[q_nope (128) | q_pe (64)]``;
* ``[c_raw | k_raw] = h W_kva``; the latent ``c = RMSNorm(c_raw)`` (512);
  ``k_pe = rope(k_raw)`` (64), one rotary key shared by the 16 heads;
* ``[k_nope | v] = c W_kvb`` per head (128 + 128): keys and values
  decompressed head by head;
* scores ``(q_nope·k_nope + rope(q_pe)·k_pe) · scale``, with ``scale =
  mscale(40, 0.707)² / sqrt(192)``; the heads' outputs through ``W_o``.

Rotary positions are YaRN's (theta 1e4, factor 40 over 4096 original
positions, beta_fast 32, beta_slow 1; mscale equal to mscale_all_dim,
so cos and sin are unscaled), computed here from the published formula.
Layer 0 has a dense SwiGLU feed-forward of 10944; layers 1-26 an MoE
layer: a softmax router over all 64 experts, greedy top-6 with the
weights left unrenormalized (``norm_topk_prob: false``, routed scaling
1), SwiGLU experts of 1408, plus two shared SwiGLU experts (one of
2816) that every token passes through. The LM head is untied.

Departures, each the program's too:

* the chip's share: experts 0-15 of each MoE layer are computed, as on
  chip 0 of four expert-parallel chips; the routed part of the others
  is left out, so a layer's output is the shared experts plus the held
  experts' weighted outputs;
* the 64 rope dims pair as rotate-half (dim i with i + 32); the
  published code pairs adjacent dims, the same model up to a fixed
  permutation of the rope columns of W_q and W_kva;
* random weights from the seed.
"""
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference.common import NEG, Arch, Layer

HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Latent:
    """The widths and settings the default layers do not hold."""

    rank: int
    nope: int
    rope: int
    v: int
    theta: float
    factor: float
    original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    held: int


def arch(cfg) -> Arch:
    m = cfg["model"]
    return Arch(num_layers=m["num_layers"], d_model=m["d_model"],
                num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
                head_dim=m["head_dim"], num_experts=m["num_experts"],
                top_k=m["top_k"], vocab_size=m["vocab_size"],
                norm="rmsnorm", norm_eps=m["norm_eps"], pos="rope",
                rope_theta=m["rope_theta"], act="swiglu", tied=False)


def latent(cfg) -> Latent:
    m = cfg["model"]
    return Latent(rank=m["kv_lora_rank"], nope=m["qk_nope_head_dim"],
                  rope=m["qk_rope_head_dim"], v=m["v_head_dim"],
                  theta=m["rope_theta"], factor=m["rope_factor"],
                  original=m["rope_original_max_position"],
                  beta_fast=m["rope_beta_fast"],
                  beta_slow=m["rope_beta_slow"], mscale=m["rope_mscale"],
                  mscale_all_dim=m["rope_mscale_all_dim"],
                  held=m["experts_held"])


# ------------------------------------------------------------------- YaRN
def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(g: Latent) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies over the rope dims."""
    dim = g.rope

    def corr(rotations):
        return (dim * math.log(g.original / (rotations * 2 * math.pi))
                / (2 * math.log(g.theta)))

    low = max(math.floor(corr(g.beta_fast)), 0)
    high = min(math.ceil(corr(g.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / g.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (extra / g.factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(g: Latent) -> float:
    return ((g.nope + g.rope) ** -0.5
            * yarn_mscale(g.factor, g.mscale_all_dim) ** 2)


def rope(g: Latent, x, pos):
    """Rotate-half YaRN rotary embedding. x (..., S, H, rope); pos (S,)."""
    ang = pos[:, None].astype(jnp.float32) * yarn_inv_freq(g)
    s = yarn_mscale(g.factor, g.mscale) / yarn_mscale(g.factor,
                                                      g.mscale_all_dim)
    cos, sin = s * jnp.cos(ang)[:, None, :], s * jnp.sin(ang)[:, None, :]
    h = g.rope // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------- attention
def project(a: Arch, g: Latent, at, h, pos, mode):
    """Rows h (S, d) at positions pos (S,): each head's query (S, H,
    nope+rope), key (S, H, nope+rope) and value (S, H, v), the keys and
    values decompressed from the latent."""
    S, H = h.shape[0], a.num_heads
    q = common.mm(h, at["wq"], mode).reshape(S, H, g.nope + g.rope)
    kva = common.mm(h, at["wkv_a"], mode)
    c = common.norm(a, at["kv_norm"], kva[:, :g.rank])
    k_pe = rope(g, kva[:, None, g.rank:], pos)                  # (S, 1, rope)
    kv = common.mm(c, at["wkv_b"], mode).reshape(S, H, g.nope + g.v)
    q = jnp.concatenate([q[..., :g.nope], rope(g, q[..., g.nope:], pos)], -1)
    k = jnp.concatenate(
        [kv[..., :g.nope], jnp.broadcast_to(k_pe, (S, H, g.rope))], -1)
    return q, k, kv[..., g.nope:]


def attend_sequence(a: Arch, g: Latent, lp, x, mode):
    """The attention half over a whole sequence x (S, d), causal: the
    post-attention residual and the layer's per-head keys and values."""
    S = x.shape[0]
    h = common.norm(a, lp["norm1"], x)
    q, k, v = project(a, g, lp["attn"], h, jnp.arange(S), mode)
    s = jnp.einsum("shd,thd->hst", q, k, precision=HI) * softmax_scale(g)
    s = jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None], s, NEG)
    o = jnp.einsum("hst,thv->shv", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(S, -1)
    return x + common.mm(o, lp["attn"]["wo"], mode), (k, v)


def attend_paths(a: Arch, g: Latent, lp, h, t, kv):
    """The attention half for P single-token paths: h (P, d) at t (P,),
    against the base pass's keys and values before t and its own."""
    K, V = kv
    hn = common.norm(a, lp["norm1"], h)
    q, k, v = jax.vmap(
        lambda r, p: project(a, g, lp["attn"], r[None], p[None], "f32"))(
            hn, t)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]                        # (P, H, .)
    sb = jnp.einsum("phd,thd->pht", q, K, precision=HI) * softmax_scale(g)
    sb = jnp.where((jnp.arange(K.shape[0])[None, :] < t[:, None])[:, None],
                   sb, NEG)
    so = jnp.einsum("phd,phd->ph", q, k,
                    precision=HI)[..., None] * softmax_scale(g)
    pr = jax.nn.softmax(jnp.concatenate([sb, so], -1), -1)
    o = (jnp.einsum("pht,thv->phv", pr[..., :-1], V, precision=HI)
         + pr[..., -1:] * v).reshape(h.shape[0], -1)
    return h + common.mm(o, lp["attn"]["wo"], "f32")


# ------------------------------------------------------------ feed-forward
def moe(a: Arch, g: Latent, p, h, logits, chosen, mode):
    """The shared experts, plus the held experts' outputs under the
    expert choice ``chosen`` (N, E), each weighted by its raw softmax
    probability over all E experts (not renormalized)."""
    w = jnp.where(chosen, jax.nn.softmax(logits, -1), 0.0)[:, :g.held]

    def one(e):
        return common.dense_ffn(a, {k: p[k][e] for k in
                                    ("w_gate", "w_up", "w_down")}, h, mode)
    out = jax.lax.map(one, jnp.arange(g.held))                 # (held, N, d)
    return (jnp.einsum("ne,end->nd", w, out, precision=HI)
            + common.dense_ffn(a, p["shared"], h, mode))


@partial(jax.jit, static_argnames=("a", "g", "mode"))
def full(a: Arch, g: Latent, lp, x, *, mode):
    lp = common.f32(lp)
    u, kv = attend_sequence(a, g, lp, x, mode)
    h2 = common.norm(a, lp["norm2"], u)
    if "moe" not in lp:
        return u + common.dense_ffn(a, lp["mlp"], h2, mode), kv, u, None
    logits = common.mm(h2, lp["moe"]["router"], mode)
    y = moe(a, g, lp["moe"], h2, logits, common.own_choice(a, logits), mode)
    return u + y, kv, u, logits


@partial(jax.jit, static_argnames=("a", "g"))
def attend(a: Arch, g: Latent, lp, h, t, kv):
    lp = common.f32(lp)
    u = attend_paths(a, g, lp, h, t, kv)
    if "moe" not in lp:
        return u, None
    return u, common.mm(common.norm(a, lp["norm2"], u), lp["moe"]["router"],
                        "f32")


@partial(jax.jit, static_argnames=("a", "g"))
def ffn(a: Arch, g: Latent, lp, u, logits, chosen):
    lp = common.f32(lp)
    h2 = common.norm(a, lp["norm2"], u)
    if "moe" not in lp:
        return u + common.dense_ffn(a, lp["mlp"], h2, "f32")
    return u + moe(a, g, lp["moe"], h2, logits, chosen, "f32")


def layers(cfg, params):
    """Layer 0 from the program's leading stack (``lead``), layers 1-26
    from its scanned stack (``blocks/pos0``)."""
    a, g = arch(cfg), latent(cfg)
    n_lead = cfg["model"]["dense_layers"]

    def layer(weights):
        return Layer(weights=weights,
                     full=lambda lp, x, mode: full(a, g, lp, x, mode=mode),
                     attend=lambda lp, h, t, kv: attend(a, g, lp, h, t, kv),
                     ffn=lambda lp, u, s, c: ffn(a, g, lp, u, s, c),
                     top_k=a.top_k)

    lead = [layer(lambda i=i: jax.tree.map(lambda t: t[i], params["lead"]))
            for i in range(n_lead)]
    return lead + [layer(common.scanned(params, "pos0", j))
                   for j in range(a.num_layers - n_lead)]
