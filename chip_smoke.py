"""Bring-up smoke test: the serving path on a TPU, at full published width.

Drives gpt2-moe (configs/paper_gpt2_moe.py: 12 layers, d 768, ff 3072,
vocab 50257, 4 experts, top-1) in bfloat16, with random weights made from
``--seed``, through the entry points a user calls. Phases, in one process:

1. device check: the first JAX device must be a TPU; there is no fallback;
2. paper pipeline + serving: ``ServerlessMoERuntime`` plans with BO, a
   default ``ServingEngine`` (fused kernels, grouped executor, telemetry)
   serves 8 ragged requests under the plan through the serving backend,
   and the runtime re-plans from the telemetry;
3. correctness: the logits of the engine's jitted prefill and decode step
   agree with ``Model.forward`` run in float32 at highest matmul precision;
4. Pallas path: ``ServingEngine(kernels="pallas")`` serves the same prompts
   through Mosaic-compiled kernels and agrees with the fused engine.

``--chips 4`` runs only the expert-parallel scatter-gather
(``repro.distributed.moe_parallel``) over four chips, against
single-device ``moe_forward``.

Any failure raises, so the exit code is non-zero and the result line is
not printed. The last line of stdout is one JSON object naming the device.

Run from the root of the checkout:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.device import enable_compile_cache  # noqa: E402

# gpt2-moe's published widths: (d_model, d_expert_ff, vocab, layers,
# experts, top_k)
GPT2_MOE_WIDTHS = (768, 3072, 50257, 12, 4, 1)

# Logits are compared as max|got - ref| <= LOGIT_RTOL * max|ref|. The
# engine keeps weights and activations in bfloat16, whose unit roundoff is
# 2**-8; each of the 12 blocks rounds its attention, norm, expert and
# residual outputs to bfloat16, and those errors add up roughly as a
# random walk over some 50 roundings (about 7 units). 2**-4 is 16 units:
# loose enough for that drift, tight enough that a wrong mask, position,
# routing decision or cache row (errors of order max|ref|) fails.
LOGIT_RTOL = 2.0 ** -4
# One operation computed two ways (expert-parallel vs single-device MoE,
# a kernel vs its jnp oracle) ends a few bfloat16 roundings apart at
# most (2**-5 is 8 units).
OP_RTOL = 2.0 ** -5

NEW_TOKENS = 16
SLOTS = 8
MAX_LEN = 1024
PROMPT_LENS = (24, 56, 96, 200, 24, 56, 96, 200)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {devices[0]}")
    if len(devices) != count:
        raise RuntimeError(f"expected {count} TPU chip(s), JAX sees "
                           f"{len(devices)}")
    return devices


def check_close(what: str, got, ref, rtol: float) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape or not np.isfinite(got).all():
        raise AssertionError(f"{what}: shape {got.shape} vs {ref.shape}, "
                             f"finite={np.isfinite(got).all()}")
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    log(f"  {what}: max|diff| {err!r} vs tolerance {rtol * scale!r} "
        f"({rtol!r} x max|ref| {scale!r})")
    if not err <= rtol * scale:
        raise AssertionError(f"{what}: max|diff| {err} > {rtol * scale}")
    return err


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def engine_logits(eng, prompt: np.ndarray, forced):
    """Logits the engine's jitted prefill and decode step give for
    ``prompt`` followed by each token of ``forced`` (teacher-forced), with
    the request in slot 0: row i predicts the token after
    ``prompt + forced[:i]``. With telemetry on, also the experts the engine
    routed every token to, (layers, tokens, top_k); else None."""
    n = len(prompt)
    logits, cache, caps = eng._jit_prefill(
        eng.params, jnp.asarray(prompt[None]), None, None, jnp.int32(n - 1))
    eng.kv.insert(cache, 0, length=n)
    rows = [np.asarray(logits[0], np.float32)]
    routes = [np.asarray(caps["pos0"]["topk_idx"][:, 0])] if caps else []
    toks = np.zeros((eng.num_slots, 1), np.int32)
    pos = np.zeros(eng.num_slots, np.int32)
    for i, tok in enumerate(forced):
        toks[0, 0], pos[0] = tok, n + i
        b = eng.kv_len_bucket
        kv_len = min(-(-(n + i + 1) // b) * b, eng.max_len)
        logits, cache, caps = eng._jit_decode(
            eng.params, jnp.asarray(toks), eng.kv.cache, jnp.asarray(pos),
            None, kv_len)
        eng.kv.update(cache)
        eng.kv.set_length(0, n + i + 1)
        rows.append(np.asarray(logits[0], np.float32))
        if caps:
            routes.append(np.asarray(caps["pos0"]["topk_idx"][:, 0]))
    eng.kv.release(0)
    return (np.stack(rows),
            np.concatenate(routes, axis=1) if routes else None)


def _reference_moe(p, cfg, h):
    """Plain float32 MoE layer (GELU experts, as in gpt2-moe) for the
    reference forward: every expert on every token, mixed by the router's
    softmax weights.

    Top-k routing is discontinuous: where bfloat16 rounding leaves two
    experts within a hair of each other, the engine and a float32 router
    may pick differently, and the logits then differ by far more than
    rounding. So the reference adopts the engine's choice
    (``p["engine_idx"]``) where its own router scores that choice within
    ``LOGIT_RTOL`` of its best, and routes by itself elsewhere: a real
    routing error still shows in the logits. ``topk_idx`` in the returned
    aux is the float32 router's own choice."""
    m = cfg.moe
    B, S, d = h.shape
    x = h.reshape(B * S, d)
    logits = x @ p["router"]
    top, own = jax.lax.top_k(logits, m.top_k)
    eng = p["engine_idx"].reshape(B * S, m.top_k)
    gap = top[:, -1:] - jnp.take_along_axis(logits, eng, 1).min(
        -1, keepdims=True)
    near = gap <= LOGIT_RTOL * jnp.abs(logits).max(-1, keepdims=True)
    idx = jnp.where(near, eng, own)
    w = jnp.take_along_axis(jax.nn.softmax(logits, -1), idx, 1)
    w = w / w.sum(-1, keepdims=True)
    hid = jax.nn.gelu(jnp.einsum("nd,edf->enf", x, p["w_in"]))
    out = jnp.moveaxis(jnp.einsum("enf,efd->end", hid, p["w_out"]), 0, 1)
    y = jnp.einsum("nkd,nk->nd",
                   jnp.take_along_axis(out, idx[..., None], 1), w)
    zero = jnp.zeros((), jnp.float32)
    aux = {"lb_loss": zero, "z_loss": zero,
           "expert_counts": jnp.zeros((m.num_experts,), jnp.int32),
           "topk_idx": own.reshape(B, S, m.top_k),
           "topk_weight": w.reshape(B, S, m.top_k)}
    return y.reshape(B, S, d), aux


def serve_phase(rc, prompts):
    """Phase 2: plan with BO, serve under the plan, re-plan from telemetry.
    Returns the runtime, the fused engine and its outputs."""
    from repro.core.runtime import ServerlessMoERuntime
    from repro.plan import Workload
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    rt = ServerlessMoERuntime(rc)
    cfg = rt.cfg
    w = rt.params["blocks"]["pos0"]["moe"]["w_in"]
    log(f"model {cfg.name}: d {cfg.d_model}, ff {cfg.moe.d_expert_ff}, "
        f"vocab {cfg.vocab_size}, {cfg.num_layers} layers, "
        f"{cfg.moe.num_experts} experts top-{cfg.moe.top_k}, "
        f"params {w.dtype} on {w.devices()}")
    plan = rt.plan_bo(Q=40, max_iters=2, seed=rc.seed)
    log(f"BO plan: methods {plan.method}, chunks {plan.chunk_schedule}")

    eng = ServingEngine(rt.model, rt.params, max_len=MAX_LEN,
                        batch_size=SLOTS)
    backend = rt.serving_backend(eng)
    rep = backend.execute(plan, Workload(batches=prompts,
                                         max_new_tokens=NEW_TOKENS))
    outs = [list(r.output) for r in backend.last_requests]
    reasons = rep.extras["finish_reasons"]
    log(f"served {len(outs)} requests (prompt lengths "
        f"{[len(p) for p in prompts]}), {rep.num_tokens} tokens, "
        f"finish reasons {reasons}")
    if reasons != ["length"] * len(prompts) or \
            any(len(o) != NEW_TOKENS for o in outs):
        raise AssertionError(f"requests not served in full: {reasons}")
    if any(not 0 <= t < cfg.vocab_size for o in outs for t in o):
        raise AssertionError("token id outside the vocabulary")
    live = rt.plan_from_telemetry(eng.telemetry)
    diff = live.metadata["replan_diff"]
    log(f"re-planned from telemetry ({eng.telemetry.prefill_tokens} "
        f"prefill + {eng.telemetry.decode_tokens} decode tokens): methods "
        f"{live.method}, {diff['replicas_changed']} replica cells changed")
    log(f"phase serve: {time.perf_counter() - t0:.1f} s wall, compilation "
        f"included; peak_bytes_in_use {peak_bytes()}")
    return rt, eng, outs


def reference_phase(rt, eng, prompt, out, steps: int = 4) -> None:
    """Phase 3: engine prefill + decode logits vs ``Model.forward`` in
    float32 on upcast weights at highest matmul precision."""
    from repro.models import Model

    t0 = time.perf_counter()
    forced = out[:steps]
    got, routes = engine_logits(eng, prompt, forced)
    seq = np.concatenate([prompt, np.asarray(forced, np.int32)])
    ref_params = jax.tree.map(lambda a: a.astype(jnp.float32), rt.params)
    ref_params["blocks"]["pos0"]["moe"]["engine_idx"] = \
        jnp.asarray(routes[:, None])
    model = Model(rt.cfg, moe_layer_fn=_reference_moe)
    with jax.default_matmul_precision("highest"):
        logits, aux, _ = jax.jit(lambda p, t: model.forward(
            p, t, capture=True))(ref_params, jnp.asarray(seq[None]))
    n = len(prompt)
    ref = np.asarray(logits[0, n - 1:n + steps, :rt.cfg.vocab_size],
                     np.float32)
    dtype = rt.params["embed"].dtype
    log(f"engine ({dtype}) vs Model.forward (float32, highest precision), "
        f"prompt of {n} tokens, prefill + {steps} decode steps:")
    own = np.asarray(aux["captures"]["pos0"]["topk_idx"][:, 0])
    log(f"  routing: the float32 router prefers another expert in "
        f"{int((own != routes).sum())} of {routes.size} decisions; the "
        f"reference follows the engine only where that is a near tie")
    check_close("logits", got, ref, LOGIT_RTOL)
    agree = int((got.argmax(-1) == ref.argmax(-1)).sum())
    log(f"  greedy tokens agree on {agree}/{len(got)} positions")
    log(f"phase reference: {time.perf_counter() - t0:.1f} s wall, "
        f"compilation included")


def assert_mosaic_kernels(eng) -> None:
    """The compiled decode step calls Mosaic kernels (``tpu_custom_call``)
    rather than inlining interpreted jnp."""
    toks = jnp.zeros((eng.num_slots, 1), jnp.int32)
    pos = jnp.zeros((eng.num_slots,), jnp.int32)
    hlo = eng._jit_decode.lower(eng.params, toks, eng.kv.cache, pos, None,
                                eng.kv_len_bucket).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("pallas decode step holds no tpu_custom_call: "
                             "the kernels were not compiled by Mosaic")
    log("pallas decode step: tpu_custom_call present (Mosaic-compiled "
        "router and flash-decode kernels)")


def kernel_oracles(cfg, dtype, seed: int = 0) -> None:
    """The Mosaic-compiled serving kernels against their jnp oracles, on
    the chip, at the engine's shapes: flash decode over a ragged
    ``SLOTS x MAX_LEN`` cache (q in the model dtype, K/V in the cache's
    float32), and fused routing over a prefill's tokens."""
    from repro.kernels.decode_attention.ops import decode_attention_pallas
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.router_topk.ops import router_topk_fused_pallas
    from repro.models.moe import route_fused

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    nh, hd = cfg.num_heads, cfg.resolved_head_dim
    q = jax.random.normal(ks[0], (SLOTS, nh, 1, hd), dtype)
    k = jax.random.normal(ks[1], (SLOTS, MAX_LEN, nh, hd), jnp.float32)
    v = jax.random.normal(ks[2], (SLOTS, MAX_LEN, nh, hd), jnp.float32)
    valid = jnp.asarray(np.linspace(1, MAX_LEN, SLOTS).astype(np.int32))
    check_close("flash-decode kernel vs oracle",
                decode_attention_pallas(q, k, v, valid),
                decode_attention_ref(q, k, v, valid), OP_RTOL)
    n = max(PROMPT_LENS)
    x = jax.random.normal(ks[3], (n, cfg.d_model), dtype)
    w = jax.random.normal(ks[4], (cfg.d_model, cfg.moe.num_experts), dtype)
    vals, idx, pos, counts, _, _ = router_topk_fused_pallas(
        x, w, k=cfg.moe.top_k)
    fr = route_fused(w, x, cfg.moe)
    for name, a, b in (("experts", idx, fr.topk_idx),
                       ("ranks", pos, fr.pos_in_e),
                       ("counts", counts, fr.expert_counts)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError(f"fused router kernel: {name} differ")
    check_close(f"fused router kernel weights ({n} tokens; experts, ranks "
                f"and counts bit-equal)", vals, fr.topk_weight, OP_RTOL)


def pallas_phase(rt, eng_fused, prompts, outs_fused) -> None:
    """Phase 4: the Mosaic-compiled kernel path against the fused path."""
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    eng = ServingEngine(rt.model, rt.params, max_len=MAX_LEN,
                        batch_size=SLOTS, kernels="pallas",
                        collect_telemetry=False)
    reqs = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    eng.run()
    outs = [list(r.output) for r in reqs]
    reasons = [r.finish_reason for r in reqs]
    if reasons != ["length"] * len(prompts):
        raise AssertionError(f"pallas engine finish reasons {reasons}")
    assert_mosaic_kernels(eng)
    same = sum(a == b for a, b in zip(outs, outs_fused))
    log(f"pallas vs fused engine: {same}/{len(prompts)} requests "
        f"token-identical")
    for p, a, b in zip(prompts, outs, outs_fused):
        if a != b:
            j = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
            log(f"  prompt of {len(p)} tokens diverges at output {j}")
            check_close("first-step logits, pallas vs fused",
                        engine_logits(eng, p, [])[0],
                        engine_logits(eng_fused, p, [])[0], LOGIT_RTOL)
    kernel_oracles(rt.cfg, rt.params["embed"].dtype)
    log(f"phase pallas: {time.perf_counter() - t0:.1f} s wall, compilation "
        f"included; peak_bytes_in_use {peak_bytes()}")


def expert_parallel_phase(cfg, devices, seed: int, tokens: int = 1024
                          ) -> None:
    """``--chips 4``: both EP scatter-gather variants at beta 1 and 4, on
    a (1, 4) mesh (one expert per chip) and a (2, 2) data x model mesh,
    against single-device grouped ``moe_forward``."""
    from jax.sharding import AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distributed.moe_parallel import (expert_parallel_moe,
                                                expert_parallel_moe_grouped)
    from repro.models.moe import init_moe, moe_forward

    t0 = time.perf_counter()
    m = cfg.moe
    # capacity = every local token: the capacity variant then drops
    # nothing and must equal the dropless reference
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        m, capacity_factor=float(m.num_experts)))
    dtype = jnp.dtype(cfg.dtype)
    kp, kx = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.tree.map(lambda a: a.astype(dtype), init_moe(kp, cfg))
    x = jax.random.normal(kx, (8, tokens // 8, cfg.d_model), dtype)
    log(f"MoE layer of {cfg.name}: d {cfg.d_model}, ff {m.d_expert_ff}, "
        f"{m.num_experts} experts top-{m.top_k}, {dtype}, {tokens} tokens")

    with jax.default_device(devices[0]):
        ref, ref_aux = jax.jit(lambda p, v: moe_forward(
            p, cfg, v, executor="grouped"))(params, x)
    ref_counts = np.asarray(ref_aux["expert_counts"])
    log(f"single-device grouped moe_forward on {devices[0]}: expert counts "
        f"{ref_counts.tolist()}")

    expert_specs = {"router": P(), "w_in": P("model", None, None),
                    "w_out": P("model", None, None)}
    for shape in ((1, 4), (2, 2)):
        mesh = jax.make_mesh(shape, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2,
                             devices=devices)
        p_sh = {k: jax.device_put(v, NamedSharding(mesh, expert_specs[k]))
                for k, v in params.items()}
        x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        log(f"mesh data x model = {shape}:")
        for name, arr in (("w_in", p_sh["w_in"]), ("x", x_sh)):
            share = sorted((s.device.id, s.data.shape)
                           for s in arr.addressable_shards)
            log(f"  {name} shards (device id, shape): {share}")
        held = {s.device.id for s in p_sh["w_in"].addressable_shards}
        if held != {d.id for d in devices}:
            raise AssertionError(f"expert weights held by {held} only")
        for fn in (expert_parallel_moe, expert_parallel_moe_grouped):
            for beta in (1, 4):
                y, aux = jax.jit(lambda p, v: fn(p, cfg, v, mesh,
                                                 beta=beta))(p_sh, x_sh)
                ys = sorted(s.device.id for s in y.addressable_shards)
                log(f"  {fn.__name__} beta={beta}: output shards on "
                    f"devices {ys}")
                check_close(f"{fn.__name__} beta={beta} vs moe_forward",
                            y, ref, OP_RTOL)
                counts = np.asarray(aux["expert_counts"])
                if not np.array_equal(counts, ref_counts):
                    raise AssertionError(f"expert counts {counts} != "
                                         f"{ref_counts}")
    log(f"phase expert-parallel: {time.perf_counter() - t0:.1f} s wall, "
        f"compilation included")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip expert-parallel path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    devices = require_tpu(args.chips)
    log(f"device {devices[0].device_kind} x {len(devices)}; JAX "
        f"{jax.__version__}; compile cache {cache_dir}")

    from repro.config import get_arch
    full = get_arch("gpt2-moe")
    widths = (full.d_model, full.moe.d_expert_ff, full.vocab_size,
              full.num_layers, full.moe.num_experts, full.moe.top_k)
    if widths != GPT2_MOE_WIDTHS or full.dtype != "bfloat16":
        raise AssertionError(f"gpt2-moe config drifted: {widths}, "
                             f"{full.dtype}")

    if args.chips == 4:
        expert_parallel_phase(full, devices, args.seed)
    else:
        from repro.core.runtime import RuntimeConfig
        rc = RuntimeConfig(arch="gpt2-moe", reduced=False,
                           profile_batches=4, learn_batches=1,
                           eval_batches=1, seq_len=128, batch_size=8,
                           seed=args.seed)
        rng = np.random.default_rng(args.seed)
        prompts = [rng.integers(0, full.vocab_size, size=n, dtype=np.int32)
                   for n in PROMPT_LENS]
        rt, eng, outs = serve_phase(rc, prompts)
        reference_phase(rt, eng, prompts[0], outs[0])
        pallas_phase(rt, eng, prompts, outs)

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
