"""Plain float32 reference for decoder-only attention + MoE models.

Independent of the program under test: it reads the weights the
benchmark made (``chipbench.weights``) in the serving parameter layout and
recomputes the model from its equations, one layer at a time, upcasting
that layer's weights to float32 and running every matrix product at
``highest`` precision. Nothing here imports ``repro``.

What it returns is, for each compared position of one sequence, the set
of logit rows the reference admits there:

* the base row: the model as written, with the reference's own routing;
* one row per admissible routing: top-k routing is discontinuous, and
  where the reference's own router holds two experts within ``near_tie``
  (in units of that token's router-logit standard deviation) of the
  top-k boundary, a program in lower precision may take either side.
  The reference then continues that token through the remaining layers
  with the other choice (and, within ``max_flips``, branches again at
  further near ties), against the base pass's keys and values.

A served token is judged by its gap: the best logit of a row minus the
token's logit in that row, taken at the admissible row where it is
smallest, in units of the base row's standard deviation.

``mode="fp8"`` is the control: the same model with both operands of
every matrix product rounded to float8 e4m3 under a per-tensor (weights)
or per-row (activations) scale, the precision below the model's bfloat16.

What every configuration shares lives here: the embedding, final norm
and head, the float32 and float8 arithmetic, the near-tie search and the
per-layer path bookkeeping. The layers themselves are a list of
:class:`Layer`, in the model's order. A configuration's reference module
(``reference/<name>.py``) may give its own with ``layers(cfg, params)``;
without it, :func:`default_layers` applies: grouped-query attention with
rotate-half rotary positions (or learned positions), then the routed MoE
layer (softmax router, top-k renormalized) or a dense feed-forward, as the
layer's parameters hold, over the program's scanned pattern.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
FP8_MAX = 448.0  # largest finite float8 e4m3fn
MAX_PATHS = 1 << 13  # no new routing paths past this many (stricter, not looser)
CHUNK = 512  # rows of every path and head program: one shape, compiled once


@dataclass(frozen=True)
class Arch:
    """What distinguishes one configuration's equations from another's."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    vocab_size: int
    norm: str            # "layernorm" | "rmsnorm"
    norm_eps: float
    pos: str             # "learned" | "rope"
    rope_theta: float
    act: str             # "gelu" (tanh form) | "swiglu"
    tied: bool


@dataclass(frozen=True)
class Layer:
    """One layer of the model, as the reference computes it.

    ``weights()`` returns the layer's parameters, taken from the model's
    when called (so that one layer's copy is alive at a time); each
    function takes them as its first argument:

    * ``full(lp, x, mode)``: the block over a whole sequence x (S, d),
      causal, in ``mode`` ("f32" or the "fp8" control). Returns the block
      output (S, d), the state the single-token paths read from this
      pass (opaque here: keys and values, or a latent cache), the
      post-attention residual (S, d), and the router's selection scores
      (S, E), or ``None`` for a layer without a router;
    * ``attend(lp, h, t, state)``: the attention half for P single-token
      paths, each with its block input h (P, d) at its position t (P,),
      against the base pass's ``state``. Returns the post-attention
      residual (P, d) and the scores (P, E) or ``None``;
    * ``ffn(lp, u, scores, chosen)``: the rest of the block for P paths
      from their residual u under the expert choice ``chosen`` (P, E)
      bool (``None`` without a router). How the choice is weighted is
      the layer's own.

    ``top_k``: the experts a token chooses, the top ``top_k`` of its
    scores, whose boundary the near-tie search reads (0 without a router).
    """

    weights: Callable[[], Any]
    full: Callable
    attend: Callable
    ffn: Callable
    top_k: int = 0


# --------------------------------------------------------------- arithmetic
def _q8(a, axis):
    """Round to float8 e4m3 under a max-abs scale along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, mode):
    """x (..., k) @ w (k, n) in float32, or with float8 operands."""
    if mode == "fp8":
        x = _q8(x, -1)
        w = _q8(w, None)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def norm(a: Arch, p, x):
    if a.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + a.norm_eps) * p["scale"] + p["bias"]
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + a.norm_eps) * p["scale"]


def rope(a: Arch, x, pos):
    """Rotate-half rotary embedding. x (..., S, H, D); pos (..., S)."""
    d = a.head_dim
    inv = 1.0 / (a.rope_theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    ang = pos[..., :, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def expert_out(a: Arch, p, h, mode):
    """Every expert on every row: h (N, d) -> (E, N, d)."""
    def one(e):
        return dense_ffn(a, {k: w[e] for k, w in p.items()
                             if k.startswith("w_")}, h, mode)
    return jax.lax.map(one, jnp.arange(a.num_experts))


def gate_weights(a: Arch, logits, chosen):
    """Router softmax over all experts, kept on ``chosen`` (N, E) bool and
    renormalized: the mixing weights, dense over experts."""
    probs = jax.nn.softmax(logits, -1)
    w = jnp.where(chosen, probs, 0.0)
    return w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)


def own_choice(a: Arch, logits):
    """The reference's top-k as an (N, E) bool mask."""
    _, idx = jax.lax.top_k(logits, a.top_k)
    return jnp.zeros(logits.shape, bool).at[
        jnp.arange(logits.shape[0])[:, None], idx].set(True)


def f32(tree):
    return jax.tree.map(lambda t: t.astype(jnp.float32), tree)


# ---------------------------------------------------------- jitted layers
@partial(jax.jit, static_argnames=("a", "mode"))
def embed(a: Arch, params, tokens, *, mode):
    x = params["embed"][tokens].astype(jnp.float32)
    if a.pos == "learned":
        x = x + params["pos_table"][: tokens.shape[0]].astype(jnp.float32)
    return x


def attend_sequence(a: Arch, lp, x, mode):
    """Grouped-query attention half of a block over a whole sequence x
    (S, d), causal, on float32 parameters ``lp``. Returns the
    post-attention residual (S, d) and this layer's keys and values
    (S, nkv, hd)."""
    S = x.shape[0]
    nh, nkv, hd = a.num_heads, a.num_kv_heads, a.head_dim
    h = norm(a, lp["norm1"], x)
    at = lp["attn"]
    q = mm(h, at["wq"], mode).reshape(S, nh, hd)
    k = mm(h, at["wk"], mode).reshape(S, nkv, hd)
    v = mm(h, at["wv"], mode).reshape(S, nkv, hd)
    if a.pos == "rope":
        pos = jnp.arange(S)
        q, k = rope(a, q, pos), rope(a, k, pos)
    g = nh // nkv
    qg = q.reshape(S, nkv, g, hd)
    s = jnp.einsum("sngd,tnd->ngst", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, NEG)
    o = jnp.einsum("ngst,tnd->sngd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST).reshape(S, nh * hd)
    return x + mm(o, at["wo"], mode), (k, v)


def attend_paths(a: Arch, lp, h, t, kv):
    """Grouped-query attention half of a block for P single-token paths
    on float32 parameters ``lp``: h (P, d) each path's block input at its
    position t (P,); ``kv``, the base pass's keys and values (S, nkv, hd)
    of this layer. A path attends to the base keys before t and to its
    own key at t. Returns the post-attention residual (P, d)."""
    K, V = kv
    P = h.shape[0]
    nh, nkv, hd = a.num_heads, a.num_kv_heads, a.head_dim
    g = nh // nkv
    hn = norm(a, lp["norm1"], h)
    at = lp["attn"]
    q = mm(hn, at["wq"], "f32").reshape(P, nh, hd)
    k = mm(hn, at["wk"], "f32").reshape(P, nkv, hd)
    v = mm(hn, at["wv"], "f32").reshape(P, nkv, hd)
    if a.pos == "rope":
        q = rope(a, q[:, None], t[:, None])[:, 0]
        k = rope(a, k[:, None], t[:, None])[:, 0]
    qg = q.reshape(P, nkv, g, hd)
    sb = jnp.einsum("pngd,tnd->pngt", qg, K,
                    precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    before = jnp.arange(K.shape[0])[None, :] < t[:, None]          # (P, S)
    sb = jnp.where(before[:, None, None, :], sb, NEG)
    so = jnp.einsum("pngd,pnd->png", qg, k,
                    precision=jax.lax.Precision.HIGHEST)[..., None] / np.sqrt(hd)
    pr = jax.nn.softmax(jnp.concatenate([sb, so], -1), -1)
    o = (jnp.einsum("pngt,tnd->pngd", pr[..., :-1], V,
                    precision=jax.lax.Precision.HIGHEST)
         + pr[..., -1:] * v[:, :, None, :]).reshape(P, nh * hd)
    return h + mm(o, at["wo"], "f32")


def dense_ffn(a: Arch, p, h, mode):
    """A dense feed-forward (the MLP's own weights ``p``) on rows h."""
    if a.act == "swiglu":
        g = mm(h, p["w_gate"], mode)
        return mm(g * jax.nn.sigmoid(g) * mm(h, p["w_up"], mode),
                  p["w_down"], mode)
    return mm(gelu_tanh(mm(h, p["w_in"], mode)), p["w_out"], mode)


def routed(a: Arch, p, h, logits, chosen, mode):
    """The routed experts' output on rows h (N, d) under the expert choice
    ``chosen`` (N, E): renormalized softmax weights over every expert."""
    w = gate_weights(a, logits, chosen)
    return jnp.einsum("ne,end->nd", w, expert_out(a, p, h, mode),
                      precision=jax.lax.Precision.HIGHEST)


@partial(jax.jit, static_argnames=("a", "mode"))
def base_layer(a: Arch, lp, x, *, mode):
    """One default block over a whole sequence x (S, d), causal: the
    :class:`Layer` ``full`` function, with keys and values as the
    state."""
    lp = f32(lp)
    u, kv = attend_sequence(a, lp, x, mode)
    h2 = norm(a, lp["norm2"], u)
    if "moe" not in lp:
        return u + dense_ffn(a, lp["mlp"], h2, mode), kv, u, None
    logits = mm(h2, lp["moe"]["router"], mode)
    y = routed(a, lp["moe"], h2, logits, own_choice(a, logits), mode)
    return u + y, kv, u, logits


@partial(jax.jit, static_argnames=("a",))
def path_attention(a: Arch, lp, h, t, kv):
    """Attention half of one default block for P single-token paths (the
    :class:`Layer` ``attend`` function): the post-attention residual
    (P, d) and the router logits (P, E), or ``None`` without a router."""
    lp = f32(lp)
    u = attend_paths(a, lp, h, t, kv)
    if "moe" not in lp:
        return u, None
    return u, mm(norm(a, lp["norm2"], u), lp["moe"]["router"], "f32")


@partial(jax.jit, static_argnames=("a",))
def path_ffn(a: Arch, lp, u, logits, chosen):
    """Feed-forward half of one default block for P paths under the given
    expert choice (the :class:`Layer` ``ffn`` function)."""
    lp = f32(lp)
    h2 = norm(a, lp["norm2"], u)
    if "moe" not in lp:
        return u + dense_ffn(a, lp["mlp"], h2, "f32")
    return u + routed(a, lp["moe"], h2, logits, chosen, "f32")


@partial(jax.jit, static_argnames=("a", "mode"))
def head(a: Arch, params, x, *, mode):
    """Final norm and LM head on rows x (R, d) -> (R, vocab)."""
    xn = norm(a, f32(params["final_norm"]), x)
    w = params["embed"].T if a.tied else params["lm_head"]
    return mm(xn, w[:, : a.vocab_size].astype(jnp.float32), mode)


# ------------------------------------------------------------- host logic
def scanned(params, key: str, j: int):
    """Layer ``j``'s parameters from a stack the program scans (a leading
    axis of layers), as a function that takes them when called."""
    return lambda: jax.tree.map(lambda t: t[j], params["blocks"][key])


def default_layers(a: Arch, params) -> List[Layer]:
    """The model's ``a.num_layers`` layers, block by block through the
    program's scanned pattern (``blocks/pos0``, ``pos1``, ...), each with
    the default equations (:func:`base_layer`, :func:`path_attention`,
    :func:`path_ffn`)."""
    n = len(params["blocks"])
    return [default_layer(a, scanned(params, f"pos{j % n}", j // n))
            for j in range(a.num_layers)]


def default_layer(a: Arch, weights: Callable[[], Any]) -> Layer:
    """A layer of grouped-query attention and the routed MoE layer (or a
    dense feed-forward, where its parameters hold no router)."""
    return Layer(weights=weights,
                 full=lambda lp, x, mode: base_layer(a, lp, x, mode=mode),
                 attend=lambda lp, h, t, kv: path_attention(a, lp, h, t, kv),
                 ffn=lambda lp, u, s, c: path_ffn(a, lp, u, s, c),
                 top_k=a.top_k)


def _chunked(fn, n_rows: int, *arrays):
    """``fn`` over host arrays (leading axis ``n_rows``) in chunks of
    ``CHUNK`` rows, the last padded with zeros, so that every program
    sees one shape and a run finds it compiled; host outputs, unpadded."""
    outs = []
    for i in range(0, n_rows, CHUNK):
        part = [np.asarray(x[i:i + CHUNK]) for x in arrays]
        part = [np.pad(x, ((0, CHUNK - len(x)),) + ((0, 0),) * (x.ndim - 1))
                for x in part]
        res = fn(*part)
        res = res if isinstance(res, tuple) else (res,)
        outs.append([np.asarray(r)[: min(CHUNK, n_rows - i)] for r in res])
    return [np.concatenate(o) for o in zip(*outs)] if outs else None


def alternatives(k: int, logits: np.ndarray, near_tie: float
                 ) -> List[Tuple[int, np.ndarray]]:
    """Near ties of the top-``k`` boundary in router scores (N, E): for
    each row, every expert choice that swaps one chosen expert for one
    left out, where both lie within ``near_tie`` standard deviations of
    the boundary. Returns (row, chosen mask) pairs."""
    out = []
    order = np.argsort(-logits, axis=1, kind="stable")
    std = logits.std(1)
    for r in range(logits.shape[0]):
        lo = logits[r, order[r, k - 1]]      # weakest chosen
        hi = logits[r, order[r, k]]          # strongest left out
        tol = near_tie * std[r]
        if lo - hi >= tol:
            continue
        ins = [e for e in order[r, :k] if logits[r, e] - hi < tol]
        outs = [e for e in order[r, k:] if lo - logits[r, e] < tol]
        base = np.zeros(logits.shape[1], bool)
        base[order[r, :k]] = True
        for i in ins:
            for o in outs:
                m = base.copy()
                m[i], m[o] = False, True
                out.append((r, m))
    return out


def admissible_rows(a: Arch, params, tokens: np.ndarray, first: int, *,
                    near_tie: float, max_flips: int = 2,
                    pad_to: int = 0, layers: Optional[List[Layer]] = None):
    """Reference logit rows for positions ``first .. len(tokens)-1`` of
    one sequence, through ``layers`` (:func:`default_layers` if not
    given).

    Returns ``(rows, row_pos)``: rows (R, vocab) on the host, float32,
    the first ``len(tokens) - first`` of them the base rows in position
    order, then one row per admissible routing path; ``row_pos`` (R,)
    gives each row's index into the compared positions."""
    if layers is None:
        layers = default_layers(a, params)
    S = len(tokens)
    S_pad = max(pad_to, S)
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = tokens
    x = embed(a, params, jnp.asarray(toks), mode="f32")
    comp = np.arange(first, S)
    # paths, on the host: positions (index into comp), hidden state, flips
    p_pos = np.zeros(0, np.int64)
    p_h = np.zeros((0, a.d_model), np.float32)
    p_flips = np.zeros(0, np.int64)
    for layer in layers:
        lp = layer.weights()
        x_next, state, u, logits = layer.full(lp, x, "f32")
        new_pos, new_h, new_flips = [], [], []
        # existing paths through the layer
        if len(p_pos) and logits is None:       # no router: no branches
            pu = _chunked(
                lambda h, t: layer.attend(lp, h, t, state)[0], len(p_pos),
                p_h, comp[p_pos].astype(np.int32))[0]
            new_pos.append(p_pos)
            new_h.append(_chunked(lambda z: layer.ffn(lp, z, None, None),
                                  len(p_pos), pu)[0])
            new_flips.append(p_flips)
        elif len(p_pos):
            pu, plog = _chunked(
                lambda h, t: layer.attend(lp, h, t, state), len(p_pos),
                p_h, comp[p_pos].astype(np.int32))
            chosen = np.zeros(plog.shape, bool)
            np.put_along_axis(chosen, np.argsort(-plog, 1)[:, : layer.top_k],
                              True, 1)
            alts = [(r, m) for r, m in alternatives(layer.top_k, plog,
                                                    near_tie)
                    if p_flips[r] < max_flips][: max(0, MAX_PATHS
                                                     - len(p_pos))]
            rows = np.concatenate([np.arange(len(p_pos)),
                                   np.asarray([r for r, _ in alts], int)])
            masks = np.concatenate([chosen] + [m[None] for _, m in alts])
            n = len(rows)
            new_pos.append(p_pos[rows])
            new_h.append(_chunked(lambda *z: layer.ffn(lp, *z), n,
                                  pu[rows], plog[rows], masks)[0])
            new_flips.append(p_flips[rows]
                             + (np.arange(n) >= len(p_pos)))
        # new paths from the base pass's near ties at compared positions
        if logits is not None:
            blog = np.asarray(logits)[first:S]
            room = MAX_PATHS - sum(len(p) for p in new_pos)
            alts = (alternatives(layer.top_k, blog, near_tie)[: max(0, room)]
                    if max_flips > 0 else [])
            if alts:
                rows = np.asarray([r for r, _ in alts], int)
                n = len(rows)
                masks = np.stack([m for _, m in alts])
                new_pos.append(rows)
                new_h.append(_chunked(lambda *z: layer.ffn(lp, *z), n,
                                      np.asarray(u)[rows + first],
                                      blog[rows], masks)[0])
                new_flips.append(np.ones(n, np.int64))
        if new_pos:
            p_pos = np.concatenate(new_pos)
            p_h = np.concatenate(new_h)
            p_flips = np.concatenate(new_flips)
        x = x_next
    R0 = S - first
    hs = np.concatenate([np.asarray(x)[first:S], p_h])
    rows = _chunked(lambda z: head(a, params, z, mode="f32"), len(hs), hs)[0]
    return rows, np.concatenate([np.arange(R0), p_pos]).astype(np.int64)


def control_tokens(a: Arch, params, tokens: np.ndarray, first: int,
                   *, pad_to: int = 0,
                   layers: Optional[List[Layer]] = None) -> np.ndarray:
    """The control: the tokens the float8 model puts first at positions
    ``first .. len(tokens)-1`` of the same sequence, through the same
    ``layers`` as :func:`admissible_rows`."""
    if layers is None:
        layers = default_layers(a, params)
    S = len(tokens)
    S_pad = max(pad_to, S)
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = tokens
    x = embed(a, params, jnp.asarray(toks), mode="fp8")
    for layer in layers:
        x = layer.full(layer.weights(), x, "fp8")[0]
    top = _chunked(lambda z: jnp.argmax(head(a, params, z, mode="fp8"), -1),
                   S - first, np.asarray(x)[first:S])[0]
    return top.astype(np.int64)


def served_gaps(rows: np.ndarray, row_pos: np.ndarray, served: np.ndarray
                ) -> np.ndarray:
    """Gap of each compared position's served token, at its most
    favourable admissible row, in units of the base row's std."""
    n = len(served)
    rows = np.asarray(rows)
    base_std = rows[:n].std(-1)
    mine = rows[np.arange(len(rows)), np.asarray(served)[row_pos]]
    g = (rows.max(-1) - mine) / base_std[row_pos]
    out = np.full(n, np.inf)
    np.minimum.at(out, row_pos, g)
    return out
