"""Continuous-batching serving engine with live expert telemetry.

The engine owns a fixed number of decode *slots* backed by one
slot-batched KV cache (:class:`SlotKVCache`). Each request is prefilled
alone at its exact prompt length (batch 1 — ragged prompts never see pad
tokens or pad attention), scattered into a free slot, and then decoded in
lock-step with every other live slot at its OWN position (the model's
vector-``pos`` decode path). Queued requests are admitted *mid-stream*
whenever a slot frees up — short requests finishing early immediately
yield capacity, unlike the old fixed-batch drain loop (kept for
comparison in ``benchmarks/serving_bench.py``).

Completion is EOS-aware (engine-level default and per-request override);
requests that exhaust ``max_new_tokens`` finish with reason ``"length"``
and requests cut off by the step budget or KV capacity are explicitly
marked ``"truncated"``.

When the model has MoE layers, an :class:`ExpertTelemetry` collector
captures per-layer routed-token counts during both prefill and decode
(the ``capture=True`` model path) — the live feedback signal
``ServerlessMoERuntime.plan_from_telemetry`` re-plans deployment from.

With an :class:`~repro.predict.online.OnlinePredictor` attached
(``predictor=...``), every decode step runs a SPECULATIVE DISPATCH
stage: before the step executes, the predictor's Eq. 1-2 posterior maps
the step's input tokens (each the PREVIOUS step's output — strictly
causal) to per-layer prewarm hints, the (layer, expert) set whose
containers a serverless deployment would warm while the non-MoE prefix
computes. After the step, the hints are scored against the routing that
actually happened (hits/misses into :class:`ExpertTelemetry`) and the
step's observations stream back into the predictor — the online
predict -> prewarm -> measure loop of the paper's §III-B, closed at
serving granularity.

Every step writes host spans into the profiler's trace (``jax.profiler``
annotations, a flag check each while no profiler runs), on the same
clock as the device's programs, so that an idle gap on the device can be
put down to what the host was doing: ``serving.step`` around a whole
step (``step_num``, ``live`` slots, the ``kv_len`` rows read),
``serving.admit`` around one admission (``uid``, ``prompt_len``,
``kind`` of prefix-cache lookup) holding ``serving.prefill``, inside
which ``serving.kv_insert`` and ``serving.device_wait`` nest;
``serving.decode`` around building a decode step's inputs and
dispatching it; ``serving.device_wait`` where the host first waits for a
program's outputs; ``serving.telemetry`` around the captures' copy to
the host and the telemetry, predictor and cache hooks; and
``serving.sample`` around the logits' copy, the argmax and the per-slot
update. ``kv_rows_read`` and ``kv_rows_live`` count the cache rows the
decode steps read (every slot up to ``kv_len``) and those of them that
live requests hold.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.dispatch.rounds import RoundAccumulator
from repro.models import Model
from repro.models.frontends import stub_frontend_embeddings
from repro.serving.kv_slots import SlotKVCache
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import Request, RequestState, SlotScheduler
from repro.serving.telemetry import ExpertTelemetry

# Hot-path kernel realizations. "fused" (default) keeps everything in
# jnp but uses the single-pass fused routing twin and ragged decode
# attention (batched decode attends only over the longest LIVE slot,
# bucketed, instead of the full max_len buffer). "pallas" additionally
# routes MoE gating through the fused Pallas router kernel and decode
# attention through the flash-decode Pallas kernel (which emits no
# attention argmax, so it runs without telemetry). "reference" is the
# original separate-pass / full-buffer path, kept as the equivalence
# baseline.
ENGINE_KERNELS = ("fused", "pallas", "reference")


class ServingEngine:
    def __init__(self, model: Model, params, *, max_len: int = 256,
                 batch_size: int = 4, eos_id: Optional[int] = None,
                 collect_telemetry: bool = True, prompt_bucket: int = 8,
                 moe_executor: str = "grouped", predictor=None,
                 cache=None, fair_aging: float = 64.0,
                 priority_aging: float = 0.0,
                 tenant_weights: Optional[Dict[str, float]] = None,
                 kernels: str = "fused", kv_len_bucket: int = 16,
                 prefix_cache_size: int = 0):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        if kernels not in ENGINE_KERNELS:
            raise ValueError(f"kernels must be one of {ENGINE_KERNELS}, "
                             f"got {kernels!r}")
        self.kernels = kernels
        self._moe_router_impl = {"fused": "fused", "pallas": "pallas",
                                 "reference": "reference"}[kernels]
        self._attn_backend = "pallas" if kernels == "pallas" else "jnp"
        # ragged decode: pass a STATIC bucketed kv-length bound to the jit
        # decode step so attention scans only the live prefix of the slot
        # buffer. Bucketing bounds recompiles to max_len / kv_len_bucket.
        self._ragged_decode = kernels != "reference"
        self.kv_len_bucket = max(1, kv_len_bucket)
        # Serving dispatches MoE layers through the DROPLESS grouped
        # ragged-GEMM path by default: under the skewed expert popularity
        # the planner exploits, the dense capacity path silently drops
        # tokens mid-stream. Passed per-call (never mutates the shared
        # Model). RoutingSummary drops (zero for "grouped") flow into the
        # telemetry's dropped_matrix.
        self.moe_executor = moe_executor if self.cfg.moe is not None \
            else None
        self.max_len = max_len
        self.batch_size = batch_size          # == number of decode slots
        self.num_slots = batch_size
        self.eos_id = eos_id
        self.scheduler = SlotScheduler(self.num_slots, aging=fair_aging,
                                       priority_aging=priority_aging,
                                       weights=tenant_weights)
        self.kv = SlotKVCache(model, self.num_slots, max_len)
        moe = self.cfg.moe
        self.telemetry: Optional[ExpertTelemetry] = (
            ExpertTelemetry(self.cfg.num_layers, moe.num_experts,
                            self.cfg.vocab_size, len(self.cfg.pattern))
            if collect_telemetry and moe is not None else None)
        self._capture = self.telemetry is not None
        if kernels == "pallas" and self._capture:
            raise ValueError(
                "kernels='pallas' decodes through the flash-decode kernel, "
                "which emits no attention argmax for telemetry; pass "
                "collect_telemetry=False")
        # speculative dispatch: an OnlinePredictor emitting per-layer
        # prewarm hints ahead of each decode step, learning online from
        # the telemetry records the step produces
        if predictor is not None and self.telemetry is None:
            raise ValueError(
                "a predictor needs expert telemetry (an MoE model and "
                "collect_telemetry=True) to score and learn from")
        self.predictor = predictor
        self.last_prewarm_hints: Optional[np.ndarray] = None
        # expert-weight residency (repro.expcache): with a cache model
        # attached, the speculative dispatch stage's prewarm hints become
        # RESIDENCY hints — hinted experts are prefetched (swapped in)
        # before the step, and each step's routed demand is scored
        # against residency (hit / swap / boot) in residency_stats()
        if cache is not None:
            if self.telemetry is None:
                raise ValueError(
                    "an expert-weight cache needs expert telemetry (an "
                    "MoE model and collect_telemetry=True) to track "
                    "residency against routed demand")
            if (cache.L, cache.E) != (self.cfg.num_layers,
                                      moe.num_experts):
                raise ValueError(
                    f"cache geometry {(cache.L, cache.E)} != model "
                    f"{(self.cfg.num_layers, moe.num_experts)}")
        self.cache = cache
        self._n_front = (self.cfg.frontend_tokens
                         if self.cfg.frontend == "vision_stub" else 0)
        self._enc_dec = self.cfg.is_encoder_decoder
        # prompt prefix cache: reuse prepared KV state across requests
        # sharing a prompt (exact) or a prompt prefix (extended by
        # teacher-forcing the suffix through the decode path). Valid only
        # for causal decoder-only stacks without frontend tokens — a
        # prefix's KV rows are then exactly the full prompt's prefix rows.
        if prefix_cache_size > 0:
            if not self.cfg.causal or self._enc_dec or self._n_front:
                raise ValueError(
                    "prefix cache requires a causal decoder-only model "
                    "without frontend tokens")
            self.prefix_cache: Optional[PrefixCache] = \
                PrefixCache(prefix_cache_size)
        else:
            self.prefix_cache = None
        # Prompt-length bucketing bounds prefill recompiles (one per bucket,
        # not one per distinct ragged length). Right-padding is invisible
        # ONLY for purely-causal full-attention DENSE stacks: causal prefill
        # never attends forward into pads, and decode's validity mask
        # excludes pad cache slots until new tokens overwrite them.
        # Recurrent state (SSM), rolling windows (swa), bidirectional
        # attention, and encoder-decoder cross caches all absorb pad
        # tokens — and MoE layers route pads through the capacity-limited
        # dispatch, where they compete with (and can evict) real tokens —
        # so all of those prefill at exact length.
        safe = (self.cfg.causal and not self._enc_dec
                and self.cfg.moe is None
                and all(s.mixer == "attn" for s in self.cfg.pattern))
        self.prompt_bucket = max(1, prompt_bucket) if safe else 1
        # per-slot decode state (host-side mirrors of the device cache)
        self.pos = np.zeros(self.num_slots, np.int32)       # next write pos
        self.cur_tok = np.zeros(self.num_slots, np.int32)   # next input tok
        self.enc_valid = np.zeros(self.num_slots, np.int32)
        self.seqs: List[np.ndarray] = [np.zeros(0, np.int64)
                                       for _ in range(self.num_slots)]
        self.step_count = 0
        # cache rows the decode steps read, and those live requests hold
        self.kv_rows_read = 0
        self.kv_rows_live = 0
        self._finished: List[Request] = []
        self._jit_prefill = jax.jit(self._prefill_impl)
        self._jit_decode = jax.jit(self._decode_impl, donate_argnums=(2,),
                                   static_argnums=(5,))
        # batch-1 teacher-forced decode for prefix-cache extension. Never
        # donates its cache argument: the stored entry cache must survive
        # to serve future hits.
        self._jit_prefix_step = jax.jit(self._prefix_step_impl)

    # ----------------------------------------------------------- jit bodies
    def _prefill_impl(self, params, toks, frontend, enc_tokens, last_idx):
        if self._capture:
            logits, cache, aux = self.model.prefill(
                params, toks, frontend=frontend, enc_tokens=enc_tokens,
                capture=True, moe_executor=self.moe_executor,
                moe_router_impl=self._moe_router_impl)
            caps = aux["captures"]
        else:
            logits, cache = self.model.prefill(
                params, toks, frontend=frontend, enc_tokens=enc_tokens,
                moe_executor=self.moe_executor,
                moe_router_impl=self._moe_router_impl)
            caps = {}
        cache = self.model.prepare_decode_cache(cache, self.max_len)
        # last REAL token's logits (bucketed prompts are right-padded),
        # restricted to the valid vocab (the head spans padded_vocab).
        return logits[:, last_idx, :self.cfg.vocab_size], cache, caps

    def _decode_impl(self, params, toks, cache, pos, cross_valid, kv_len):
        if self._capture:
            logits, cache, caps = self.model.decode_step(
                params, toks, cache, pos, capture=True,
                cross_valid=cross_valid, moe_executor=self.moe_executor,
                moe_router_impl=self._moe_router_impl, kv_len=kv_len,
                attn_backend=self._attn_backend)
        else:
            logits, cache = self.model.decode_step(
                params, toks, cache, pos, cross_valid=cross_valid,
                moe_executor=self.moe_executor,
                moe_router_impl=self._moe_router_impl, kv_len=kv_len,
                attn_backend=self._attn_backend)
            caps = {}
        # never emit padding-vocab ids: they corrupt telemetry keying and
        # downstream consumers of Request.output
        return logits[:, -1, :self.cfg.vocab_size], cache, caps

    def _prefix_step_impl(self, params, tok, cache, pos):
        # plain jnp attention: batch-1 single-token steps are launch-bound,
        # not a kernel target; router impl still follows the engine knob so
        # extension reproduces exactly what prefill would have routed.
        logits, cache = self.model.decode_step(
            params, tok, cache, pos, moe_executor=self.moe_executor,
            moe_router_impl=self._moe_router_impl)
        return logits[:, -1, :self.cfg.vocab_size], cache

    @property
    def pending(self) -> int:
        """Requests submitted but not yet admitted to a slot."""
        return len(self.scheduler.queue)

    # --------------------------------------------------------------- submit
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               tenant: Optional[str] = None,
               priority: int = 0) -> Request:
        prompt = np.asarray(prompt, np.int32).ravel()
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if len(prompt) + self._n_front >= self.max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens (+{self._n_front} frontend)"
                f" does not fit max_len={self.max_len}")
        if self._enc_dec and self.cfg.encoder is not None:
            if len(prompt) > self.cfg.encoder.source_len:
                raise ValueError("prompt exceeds encoder source_len")
        return self.scheduler.submit(prompt, max_new_tokens, eos_id=eos_id,
                                     tenant=tenant, priority=priority,
                                     submit_step=self.step_count)

    # ------------------------------------------------------------ admission
    def _prefill_kwargs(self, prompt: np.ndarray) -> Dict[str, Any]:
        kw: Dict[str, Any] = {"frontend": None, "enc_tokens": None}
        if self.cfg.frontend in ("vision_stub", "audio_stub"):
            kw["frontend"] = stub_frontend_embeddings(self.cfg, 1)
        elif self._enc_dec:
            kw["enc_tokens"] = jnp.asarray(prompt[None])
        return kw

    def _sliced_prefill_captures(self, caps, true_len: int) -> Dict:
        """Trim captures to the real token span so feature extraction sees
        token-aligned arrays: drop prepended frontend positions (vision
        models) and right-pad positions (bucketed prompts)."""
        nf = self._n_front
        out = {}
        for key, cap in caps.items():
            c = dict(cap)
            if "topk_idx" in c:
                c["topk_idx"] = c["topk_idx"][:, :, nf:nf + true_len]
                c["topk_weight"] = c["topk_weight"][:, :, nf:nf + true_len]
            if "attn_argmax" in c:
                # causal ⇒ argmax key pos <= query pos, so real queries only
                # point at real (frontend-offset) positions.
                c["attn_argmax"] = np.maximum(
                    c["attn_argmax"][:, :, nf:nf + true_len] - nf, 0)
            out[key] = c
        return out

    def _finish(self, req: Request, reason: str) -> None:
        self.scheduler.finish(req, reason)
        self._finished.append(req)

    def _admit(self) -> bool:
        """Prefill queued requests into free slots. Returns True if any."""
        admitted = False
        while self.scheduler.queue:
            free = self.scheduler.free_slots()
            if not free:
                break
            slot = free[0]
            req = self.scheduler.admit_next(slot, self.step_count)
            assert req is not None
            with TraceAnnotation("serving.admit", uid=req.uid,
                                 prompt_len=len(req.prompt)) as span:
                self._admit_one(req, slot, span)
            admitted = True
        return admitted

    def _admit_one(self, req: Request, slot: int,
                   span: TraceAnnotation) -> None:
        """Prefill ``req`` (or take its cache from the prefix cache) into
        ``slot`` and emit its first token."""
        kw = self._prefill_kwargs(req.prompt)
        true_len = len(req.prompt)
        s_tot = true_len + self._n_front
        pc_kind, pc_entry = "miss", None
        if self.prefix_cache is not None:
            pc_kind, pc_entry = self.prefix_cache.lookup(req.prompt)
            if pc_kind == "prefix" and self.telemetry is not None:
                # extension teacher-forces the suffix without capture,
                # so it cannot replay routing records — with telemetry
                # on, only exact hits skip the prefill
                pc_kind, pc_entry = "miss", None
        span.set_metadata(kind=pc_kind)
        caps_sliced: Dict[str, Any] = {}
        if pc_kind == "exact":
            # prefill is deterministic, so the stored prepared cache +
            # last-token logits (and sliced captures, for telemetry
            # replay) are bit-identical to re-prefilling this prompt
            with TraceAnnotation("serving.kv_insert"):
                self.kv.insert(pc_entry.cache, slot, length=s_tot)
            last_np = pc_entry.last_logits
            caps_sliced = pc_entry.caps or {}
        elif pc_kind == "prefix":
            # extend the longest stored prefix by teacher-forcing the
            # unseen suffix through the decode path, one token a step
            with TraceAnnotation("serving.prefill"):
                cache = pc_entry.cache
                logits = None
                for t in range(len(pc_entry.prompt), true_len):
                    logits, cache = self._jit_prefix_step(
                        self.params,
                        jnp.asarray(req.prompt[t][None, None]),
                        cache, jnp.int32(t))
                with TraceAnnotation("serving.device_wait"):
                    jax.block_until_ready(logits)
                last_np = np.asarray(logits)[0]
            self.prefix_cache.put(req.prompt, cache, last_np)
            with TraceAnnotation("serving.kv_insert"):
                self.kv.insert(cache, slot, length=s_tot)
        else:
            bucket = self.prompt_bucket
            padded = -(-true_len // bucket) * bucket
            # prefilled cache (padded + frontend) must fit the slot
            padded = min(padded, self.max_len - self._n_front)
            toks = np.zeros(padded, np.int32)
            toks[:true_len] = req.prompt
            with TraceAnnotation("serving.prefill"):
                last_logits, cache, caps = self._jit_prefill(
                    self.params, jnp.asarray(toks[None]),
                    kw["frontend"], kw["enc_tokens"],
                    jnp.int32(self._n_front + true_len - 1))
                with TraceAnnotation("serving.kv_insert"):
                    self.kv.insert(cache, slot, length=s_tot)
                with TraceAnnotation("serving.device_wait"):
                    jax.block_until_ready((last_logits, caps))
                last_np = np.asarray(last_logits)[0]
            if self.telemetry is not None:
                with TraceAnnotation("serving.telemetry"):
                    caps_h = jax.tree.map(np.asarray, caps)
                    caps_sliced = self._sliced_prefill_captures(
                        caps_h, true_len)
            if self.prefix_cache is not None:
                self.prefix_cache.put(
                    req.prompt, cache, last_np,
                    caps_sliced if self.telemetry is not None
                    else None)
        self.pos[slot] = s_tot
        if self._enc_dec:
            if self.cfg.frontend == "audio_stub":
                self.enc_valid[slot] = self.cfg.frontend_tokens
            else:
                self.enc_valid[slot] = len(req.prompt)
        if self.telemetry is not None:
            with TraceAnnotation("serving.telemetry"):
                mark = self.telemetry.num_records
                self.telemetry.record_prefill(req.prompt[None], caps_sliced)
                if self.predictor is not None:
                    # prefill feeds learning only; hints are a decode-
                    # step concern (prefill routes are observed wholesale)
                    self.predictor.observe_tokens(req.prompt)
                    self.predictor.update_records(
                        self.telemetry.records_since(mark))
        first = int(last_np.argmax())
        req.first_token_time = time.perf_counter()
        if req.max_new_tokens < 1:
            self.seqs[slot] = req.prompt.astype(np.int64)
            self._finish(req, "length")
            self.kv.release(slot)
        else:
            req.output.append(first)
            self.seqs[slot] = np.append(req.prompt.astype(np.int64),
                                        first)
            self.cur_tok[slot] = first
            eos = req.eos_id if req.eos_id is not None else self.eos_id
            if eos is not None and first == eos:
                self._finish(req, "eos")
                self.kv.release(slot)
            elif len(req.output) >= req.max_new_tokens:
                self._finish(req, "length")
                self.kv.release(slot)

    # ------------------------------------------------------------------ step
    def step(self) -> bool:
        """Admit queued requests, then advance every live slot one token.

        Returns False when there was nothing to do."""
        with StepTraceAnnotation("serving.step",
                                 step_num=self.step_count) as span:
            return self._step(span)

    def _step(self, span: StepTraceAnnotation) -> bool:
        self._admit()
        active = [i for i, r in enumerate(self.scheduler.slots)
                  if r is not None]
        if not active:
            return False
        in_tok = self.cur_tok.copy()
        in_pos = self.pos.copy()
        # --- speculative dispatch: hints from the step's INPUT tokens
        # (the previous step's outputs), emitted before routing runs
        hints = None
        if self.predictor is not None:
            with TraceAnnotation("serving.telemetry"):
                act_tok = in_tok[np.asarray(active, np.int64)]
                hints = self.predictor.prewarm_hint_matrix(act_tok)
                self.last_prewarm_hints = hints
                if self.cache is not None:
                    # residency hints: swap hinted experts in BEFORE the
                    # step's routing runs, so predicted-hot experts are
                    # already warm
                    self.cache.prefetch(hints)
        with TraceAnnotation("serving.decode"):
            cross_valid = (jnp.asarray(self.enc_valid) if self._enc_dec
                           else None)
            # ragged decode: a static attention bound covering the longest
            # live slot AFTER this step's write (max valid rows + 1),
            # rounded up to kv_len_bucket so recompiles stay bounded. Dead
            # slots' rows are released, so the bound tracks live requests
            # only.
            kv_len = None
            if self._ragged_decode:
                need = self.kv.max_valid_len() + 1
                b = self.kv_len_bucket
                kv_len = min(-(-need // b) * b, self.max_len)
            # every slot reads its rows up to the bound; after the write a
            # live slot holds pos + 1 of them
            rows = self.max_len if kv_len is None else kv_len
            self.kv_rows_read += self.num_slots * rows
            self.kv_rows_live += int(in_pos[active].sum()) + len(active)
            span.set_metadata(live=len(active), kv_len=rows)
            logits, cache, caps = self._jit_decode(
                self.params, jnp.asarray(in_tok[:, None]), self.kv.cache,
                jnp.asarray(in_pos), cross_valid, kv_len)
            self.kv.update(cache)
        with TraceAnnotation("serving.device_wait"):
            jax.block_until_ready((logits, caps))
        if self.telemetry is not None:
            with TraceAnnotation("serving.telemetry"):
                caps_h = jax.tree.map(np.asarray, caps)
                demand_before = (self.telemetry.demand.copy()
                                 if hints is not None or self.cache is not None
                                 else None)
                mark = self.telemetry.num_records
                self.telemetry.record_decode(
                    in_tok, in_pos - self._n_front, self.seqs, caps_h,
                    active, n_front=self._n_front)
                if self.cache is not None:
                    # score the step's ACTUAL routing against residency
                    self.cache.serve_demand(
                        self.telemetry.demand - demand_before)
                if hints is not None:
                    # score the hints against what the step actually
                    # routed, THEN learn from the step (hints stay
                    # strictly causal)
                    self.telemetry.record_prewarm(
                        hints, self.telemetry.demand - demand_before)
                    self.predictor.observe_tokens(
                        in_tok[np.asarray(active, np.int64)])
                    self.predictor.update_records(
                        self.telemetry.records_since(mark))
        with TraceAnnotation("serving.sample"):
            nxt = np.asarray(logits).argmax(-1)
            for i in active:
                req = self.scheduler.slots[i]
                assert req is not None
                tok = int(nxt[i])
                req.output.append(tok)
                self.seqs[i] = np.append(self.seqs[i], tok)
                self.pos[i] += 1
                self.cur_tok[i] = tok
                self.kv.set_length(i, int(self.pos[i]))
                eos = req.eos_id if req.eos_id is not None else self.eos_id
                if eos is not None and tok == eos:
                    self._finish(req, "eos")
                    self.kv.release(i)
                elif len(req.output) >= req.max_new_tokens:
                    self._finish(req, "length")
                    self.kv.release(i)
                elif self.pos[i] >= self.max_len:
                    self._finish(req, "truncated")   # KV capacity exhausted
                    self.kv.release(i)
        self.step_count += 1
        return True

    # ------------------------------------------------------------ speculation
    def speculation_stats(self) -> Dict[str, Any]:
        """Scoreboard of the speculative dispatch stage: how often the
        predictor's prewarm hints covered the routing that actually
        happened (``hit_rate`` is None before any scored decode step)."""
        tel = self.telemetry
        if tel is None:
            raise ValueError("speculation stats need expert telemetry")
        per_layer = np.divide(
            tel.prewarm_hits_by_layer, tel.prewarm_pairs_by_layer,
            out=np.zeros_like(tel.prewarm_hits_by_layer),
            where=tel.prewarm_pairs_by_layer > 0)
        return {
            "hits": tel.prewarm_hits,
            "misses": tel.prewarm_misses,
            "pairs": tel.prewarm_pairs,
            "hit_rate": tel.prewarm_hit_rate(),
            "per_layer_hit_rate": per_layer.tolist(),
        }

    def residency_stats(self) -> Dict[str, Any]:
        """Scoreboard of the expert-weight cache: residency hits, swaps
        (including speculative prefetch swaps), boots, evictions, and
        current resident/packed expert counts."""
        if self.cache is None:
            raise ValueError("residency stats need an expert-weight "
                             "cache (ServingEngine(cache=...))")
        return self.cache.residency_stats()

    # ------------------------------------------------------------------- run
    def run(self, *, max_steps: int = 256, on_step=None,
            round_tokens: int = 0, on_round=None,
            arrivals=None) -> List[Request]:
        """Serve until queue and slots drain (or ``max_steps`` decode steps).

        ``on_step(engine, step_index)`` runs after every decode step —
        submitting new requests from it exercises mid-stream admission.
        When the step budget runs out, requests still HOLDING SLOTS are
        finished with ``finish_reason="truncated"``; requests never
        admitted stay queued (``scheduler.queue``) and are served by the
        next ``run()`` call. Returns requests finished during this call,
        in completion order.

        ``round_tokens > 0`` segments serving into scatter-gather
        dispatch rounds (requires telemetry): once at least that many
        tokens have been served since the round opened, the round closes
        and ``on_round(engine, {"steps", "tokens"})`` fires — the
        execution granularity a ``DeploymentPlan``'s pipeline chunk
        schedule prescribes (``repro.plan.backends.ServingBackend``).

        ``arrivals`` is an optional timed request schedule (objects with
        ``arrival_step``/``prompt``/``max_new_tokens``, e.g.
        :class:`repro.traces.TraceRequest`): each request is submitted
        once the arrival clock reaches its arrival step, so bursty
        traces drive queueing and mid-stream admission. The clock
        advances one tick per decode step; idle gaps (no live work
        before the next arrival) fast-forward the clock WITHOUT burning
        the ``max_steps`` decode budget. Arrivals still due when the
        budget runs out are submitted into the queue on exit (never
        silently dropped) and served by the next ``run()`` call."""
        if round_tokens and self.telemetry is None:
            raise ValueError("round_tokens requires expert telemetry")
        mark = len(self._finished)
        # round segmentation lives in the shared dispatch substrate so
        # every execution surface splits token streams identically
        rounds = RoundAccumulator(
            round_tokens,
            start_tokens=(self.telemetry.total_tokens
                          if self.telemetry is not None else 0),
            on_round=on_round)

        queue_arr = sorted(arrivals, key=lambda r: r.arrival_step) \
            if arrivals else []
        arr_i = 0

        def _submit_due(step: int) -> None:
            nonlocal arr_i
            while arr_i < len(queue_arr) \
                    and queue_arr[arr_i].arrival_step <= step:
                r = queue_arr[arr_i]
                self.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                            tenant=getattr(r, "tenant", None),
                            priority=getattr(r, "priority", 0))
                arr_i += 1

        _submit_due(0)
        self._admit()      # prefill-only / instant-EOS requests complete here
        steps = 0          # decode budget: real decode steps only
        clock = 0          # arrival time: advances with decode steps AND
        #                    fast-forwards across idle gaps
        while steps < max_steps:
            _submit_due(clock)
            if not self.scheduler.has_work:
                if arr_i < len(queue_arr):
                    # idle gap: jump the clock to the next arrival
                    clock = max(clock + 1,
                                queue_arr[arr_i].arrival_step)
                    continue
                break
            if not self.step():
                # nothing was decodable (e.g. every admitted request
                # finished instantly at prefill): fall through to the
                # top, which re-checks pending arrivals before quitting
                continue
            steps += 1
            clock += 1
            rounds.record_step()
            if on_step is not None:
                on_step(self, steps)
            if rounds.due(self.telemetry.total_tokens
                          if self.telemetry is not None else 0):
                rounds.close(self.telemetry.total_tokens, self)
        if rounds.pending(self.telemetry.total_tokens
                          if self.telemetry is not None else 0):
            rounds.close(self.telemetry.total_tokens, self)  # final partial
        # arrivals the budget never reached: queue them (not dropped) so
        # the next run() serves them
        while arr_i < len(queue_arr):
            r = queue_arr[arr_i]
            self.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                        tenant=getattr(r, "tenant", None),
                        priority=getattr(r, "priority", 0))
            arr_i += 1
        if self.scheduler.has_work:
            for i, slot_req in enumerate(self.scheduler.slots):
                if slot_req is not None:
                    self.kv.release(i)
            for req in list(self.scheduler.active()):
                self._finish(req, "truncated")
        return self._finished[mark:]
