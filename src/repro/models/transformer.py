"""Composable model: embeddings -> scanned block stack -> LM head.

Supports every assigned architecture family:

* decoder-only dense / MoE / SSM / hybrid stacks (scan over the repeating
  ``cfg.pattern`` unit so compile time is O(|pattern|), not O(num_layers));
* encoder-decoder (whisper, bert2bert) with cross-attention caches;
* bidirectional encoders (bert-moe, ``cfg.causal=False``);
* multimodal stubs: frontend embeddings prepended (VLM) or fed to the
  encoder (audio).

Prefill and decode run the embedding under the name scope ``embed`` and
the final norm and LM head under ``head`` (``jax.named_scope``; the
blocks add ``attention`` and ``moe``), so the profiler's trace can put
each device op down to the part of the model it belongs to.

Params are plain nested dicts. Block params are stacked along a leading
``num_blocks`` axis; zamba-style shared weights live under ``params["shared"]``
and are closed over (never stacked).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import blocks as B
from repro.models.common import (Params, apply_norm,
                                 chunked_head_cross_entropy, cross_entropy,
                                 embed_init, init_norm, split_keys)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


class Model:
    """Functional model wrapper bound to a :class:`ModelConfig`."""

    def __init__(self, cfg: ModelConfig, *, expert_pad_multiple: int = 1,
                 moe_ffn_fn=None, moe_layer_fn=None,
                 moe_executor: str = "dense", moe_grouped_fn=None,
                 moe_router_impl: str = "fused", attn_backend: str = "jnp",
                 remat: bool = True):
        self.cfg = cfg
        self.expert_pad_multiple = expert_pad_multiple
        self.moe_ffn_fn = moe_ffn_fn
        self.moe_layer_fn = moe_layer_fn   # replaces the whole MoE layer
        # default MoE dispatch path ("dense" | "grouped" | "oracle");
        # forward/prefill/decode_step accept a per-call override so e.g.
        # the serving engine can pick the dropless grouped path without
        # mutating a shared Model instance
        self.moe_executor = moe_executor
        self.moe_grouped_fn = moe_grouped_fn
        # routing front-end ("fused" | "reference" | "pallas") and decode
        # attention realization ("jnp" | "pallas") — same per-call
        # override convention as moe_executor
        self.moe_router_impl = moe_router_impl
        self.attn_backend = attn_backend
        self.remat = remat   # checkpoint each block in the training path
        self.decode_dense_threshold = 4096  # see attention_decode_step
        self.num_experts_padded = (
            _round_up(cfg.moe.num_experts, expert_pad_multiple)
            if cfg.moe is not None else 0)

    # ------------------------------------------------------------------ init
    def init_params(self, key: jax.Array, dtype=jnp.float32) -> Params:
        cfg = self.cfg
        ks = split_keys(key, 8)
        params: Params = {
            "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype),
            "final_norm": init_norm(cfg.norm, cfg.d_model),
        }
        if cfg.pos_embed == "learned":
            params["pos_table"] = embed_init(
                ks[1], (cfg.max_seq_len, cfg.d_model), dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = embed_init(
                ks[2], (cfg.d_model, cfg.padded_vocab), dtype)
        params["shared"] = B.init_shared(ks[3], cfg)

        cross = cfg.is_encoder_decoder
        blk: Dict[str, Params] = {}
        for p, spec in enumerate(cfg.pattern):
            keys = jax.random.split(jax.random.fold_in(ks[4], p),
                                    cfg.num_blocks)
            blk[f"pos{p}"] = jax.vmap(
                lambda k, spec=spec: B.init_block(
                    k, cfg, spec, cross_attention=cross,
                    num_experts=self.num_experts_padded or None)
            )(keys)
        params["blocks"] = blk

        if cfg.encoder is not None:
            e = cfg.encoder
            import dataclasses
            enc_cfg = dataclasses.replace(
                cfg, num_heads=e.num_heads, num_kv_heads=e.num_heads,
                head_dim=cfg.d_model // e.num_heads, d_ff=e.d_ff,
                causal=False, qk_norm=False)
            from repro.config import LayerSpec
            enc_spec = LayerSpec("attn", "dense")
            keys = jax.random.split(ks[5], e.num_layers)
            params["encoder"] = {
                "blocks": jax.vmap(
                    lambda k: B.init_block(k, enc_cfg, enc_spec))(keys),
                "final_norm": init_norm(cfg.norm, cfg.d_model),
                "pos_table": embed_init(
                    ks[6], (max(e.source_len, cfg.max_seq_len), cfg.d_model),
                    dtype),
            }
            self._enc_cfg, self._enc_spec = enc_cfg, enc_spec
        if dtype != jnp.float32:
            params = jax.tree.map(
                lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a,
                params)
        return params

    # --------------------------------------------------------------- encoder
    def encode(self, params: Params, enc_input: jnp.ndarray) -> jnp.ndarray:
        """enc_input: (B, F, d) frontend embeddings or (B, Se) token ids."""
        cfg = self.cfg
        assert cfg.encoder is not None
        enc = params["encoder"]
        if enc_input.ndim == 2:    # token ids (bert2bert)
            x = jnp.take(params["embed"], enc_input, axis=0)
        else:
            x = enc_input
        F = x.shape[1]
        x = x + enc["pos_table"][:F]
        positions = jnp.arange(F)

        def body(h, blk_p):
            h, _, _ = B.block_forward(blk_p, {}, self._enc_cfg,
                                      self._enc_spec, h, positions=positions)
            return h, None

        x, _ = jax.lax.scan(body, x, enc["blocks"])
        return apply_norm(cfg.norm, enc["final_norm"], x)

    # --------------------------------------------------------------- forward
    def forward(
        self,
        params: Params,
        tokens: jnp.ndarray,                       # (B, S)
        *,
        frontend: Optional[jnp.ndarray] = None,    # (B, F, d) stub embeddings
        enc_tokens: Optional[jnp.ndarray] = None,  # (B, Se) for bert2bert
        capture: bool = False,
        return_cache: bool = False,
        hidden_only: bool = False,
        moe_executor: Optional[str] = None,
        moe_router_impl: Optional[str] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, Any], Any]:
        """Returns (logits, aux, cache). ``aux`` carries MoE losses and,
        under ``capture``, per-block routing/attention features.
        ``hidden_only`` skips the LM head (the loss fuses head+CE).
        ``moe_executor`` / ``moe_router_impl`` override the model's MoE
        dispatch path / routing front-end for this call."""
        cfg = self.cfg
        executor = moe_executor or self.moe_executor
        router_impl = moe_router_impl or self.moe_router_impl
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
            n_front = 0
            if cfg.frontend == "vision_stub" and frontend is not None:
                x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
                n_front = frontend.shape[1]
            S = x.shape[1]
            positions = jnp.arange(S)
            if cfg.pos_embed == "learned":
                x = x + params["pos_table"][:S]

        enc_out = None
        if cfg.is_encoder_decoder:
            enc_in = frontend if cfg.frontend == "audio_stub" else enc_tokens
            assert enc_in is not None, "encoder-decoder model needs source"
            enc_out = self.encode(params, enc_in)

        shared = params["shared"]

        def body(h, blk_params):
            caches, caps = {}, {}
            for p, spec in enumerate(cfg.pattern):
                h, c, cap = B.block_forward(
                    blk_params[f"pos{p}"], shared, cfg, spec, h,
                    positions=positions, enc_out=enc_out, capture=capture,
                    return_cache=return_cache, moe_ffn_fn=self.moe_ffn_fn,
                    moe_layer_fn=self.moe_layer_fn,
                    moe_executor=executor,
                    moe_grouped_fn=self.moe_grouped_fn,
                    moe_router_impl=router_impl)
                caches[f"pos{p}"] = c
                caps[f"pos{p}"] = cap
            return h, (caches, caps)

        if self.remat and not (capture or return_cache):
            body = jax.checkpoint(body)   # activation remat per block
        x, (cache, caps) = jax.lax.scan(body, x, params["blocks"])
        with jax.named_scope("head"):
            x = apply_norm(cfg.norm, params["final_norm"], x)

        aux: Dict[str, Any] = {"n_front": n_front}
        lb = z = 0.0
        counts = []
        for p, spec in enumerate(cfg.pattern):
            cp = caps[f"pos{p}"]
            if "lb_loss" in cp:
                lb = lb + cp["lb_loss"].sum()
                z = z + cp["z_loss"].sum()
                counts.append(cp["expert_counts"])
        aux["lb_loss"], aux["z_loss"] = jnp.asarray(lb), jnp.asarray(z)
        if counts:
            aux["expert_counts"] = jnp.stack(counts, 1)  # (nb, n_moe_pos, E)
        if capture:
            aux["captures"] = caps
        if hidden_only:
            return x, aux, (cache if return_cache else None)
        with jax.named_scope("head"):
            logits = x @ self.head_weight(params)
        return logits, aux, (cache if return_cache else None)

    def head_weight(self, params: Params) -> jnp.ndarray:
        return (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])

    # ------------------------------------------------------------------ loss
    def loss(self, params: Params, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        cfg = self.cfg
        x, aux, _ = self.forward(
            params, batch["tokens"],
            frontend=batch.get("frontend"),
            enc_tokens=batch.get("enc_tokens"),
            hidden_only=True)
        labels = batch["labels"]
        if batch.get("label_mask") is not None:
            labels = jnp.where(batch["label_mask"] > 0, labels, -1)
        if aux["n_front"]:
            x = x[:, aux["n_front"]:]
        ce = chunked_head_cross_entropy(
            x, self.head_weight(params), labels, valid_vocab=cfg.vocab_size)
        total = ce + aux["lb_loss"] + aux["z_loss"]
        return total, {"ce": ce, "lb": aux["lb_loss"], "z": aux["z_loss"]}

    # --------------------------------------------------------------- serving
    def init_cache(self, batch: int, seq_len: int, *,
                   dtype=jnp.float32) -> Dict[str, Any]:
        """Zero decode cache, stacked (num_blocks, ...) per unit position.

        Self-attention K/V (full, windowed ``swa`` and ``shared_attn``
        mixers) are (num_blocks, B, T, n_kv·hd): heads and head dim fused
        into the minor axis, the layout :meth:`decode_step` carries
        through its layer scan and writes in place; cross-attention K/V
        share it, recurrent states keep their own shapes."""
        cfg = self.cfg
        cross_len = cfg.encoder.source_len if cfg.is_encoder_decoder else 0

        def stack(tree):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a, (cfg.num_blocks,) + a.shape),
                tree)

        return {f"pos{p}": stack(B.init_block_cache(
                    cfg, spec, batch, seq_len, cross_len=cross_len,
                    dtype=dtype))
                for p, spec in enumerate(cfg.pattern)}

    def prepare_decode_cache(self, cache: Dict[str, Any],
                             max_len: int) -> Dict[str, Any]:
        """Pad prefill caches to decode-buffer sizes.

        Full-attention K/V, (num_blocks, B, T, n_kv·hd) as
        :meth:`init_cache` lays them out, grow along ``T`` from the
        prefilled length to ``max_len`` (zeros beyond the valid prefix are
        masked by position validity); rolling-window caches pad up to
        ``window`` slots; recurrent states and cross caches pass through
        unchanged.
        """
        cfg = self.cfg
        out: Dict[str, Any] = {}
        for p, spec in enumerate(cfg.pattern):
            cp = dict(cache[f"pos{p}"])
            if "attn" in cp:
                window = cfg.sliding_window if spec.mixer == "swa" else 0
                target = min(window, max_len) if window > 0 else max_len
                kv = {}
                for kname, arr in cp["attn"].items():
                    T = arr.shape[2]   # (num_blocks, B, T, nkv*hd)
                    if T < target:
                        pad = [(0, 0)] * arr.ndim
                        pad[2] = (0, target - T)
                        arr = jnp.pad(arr, pad)
                    kv[kname] = arr
                cp["attn"] = kv
            out[f"pos{p}"] = cp
        return out

    def prefill(self, params: Params, tokens: jnp.ndarray, *,
                frontend=None, enc_tokens=None, capture: bool = False,
                moe_executor: Optional[str] = None,
                moe_router_impl: Optional[str] = None):
        """Full-sequence pass that returns (logits, cache) for decoding.

        With ``capture=True`` returns (logits, cache, aux) where ``aux``
        carries the per-block routing/attention captures (the serving
        engine's telemetry source)."""
        logits, aux, cache = self.forward(
            params, tokens, frontend=frontend, enc_tokens=enc_tokens,
            return_cache=True, capture=capture, moe_executor=moe_executor,
            moe_router_impl=moe_router_impl)
        if capture:
            return logits, cache, aux
        return logits, cache

    def decode_step(self, params: Params, tokens: jnp.ndarray,
                    cache: Dict[str, Any], pos, *,
                    capture: bool = False, cross_valid=None,
                    moe_executor: Optional[str] = None,
                    moe_router_impl: Optional[str] = None,
                    kv_len: Optional[int] = None,
                    attn_backend: Optional[str] = None):
        """One-token step. tokens: (B, 1); ``pos``: absolute position —
        scalar (whole batch) or a (B,) vector of per-slot positions for
        ragged continuous batching. Returns (logits, new_cache), or
        (logits, new_cache, captures) under ``capture`` where ``captures``
        maps ``pos{p}`` -> stacked (num_blocks, ...) routing/attention
        captures. ``cross_valid`` masks encoder padding per row (enc-dec
        slots prefilled from ragged sources). ``kv_len``: static promise
        that every row's ``pos + 1 <= kv_len`` this step, letting
        full-attention layers score a sliced cache instead of the whole
        ``max_len`` buffer (callers re-jit per distinct value — bucket
        it). ``attn_backend``: "jnp" | "pallas" decode attention.

        The layer scan carries the self-attention K/V stacks,
        (num_blocks, B, T, n_kv·hd), with the scanned block index, and each
        block writes its new row into them in place: no layer's cache is
        sliced out at ``T`` and stacked back. Recurrent states and
        read-only cross caches are scanned per block."""
        cfg = self.cfg
        executor = moe_executor or self.moe_executor
        router_impl = moe_router_impl or self.moe_router_impl
        backend = attn_backend or self.attn_backend
        pos = jnp.asarray(pos)
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0)
            if cfg.pos_embed == "learned":
                if pos.ndim == 1:  # per-slot positions: (B,) -> (B, 1, d)
                    x = x + jnp.take(params["pos_table"], pos,
                                     axis=0)[:, None]
                else:
                    x = x + jax.lax.dynamic_slice_in_dim(
                        params["pos_table"], pos, 1, axis=0)
        shared = params["shared"]
        carried = {f"pos{p}" for p, spec in enumerate(cfg.pattern)
                   if spec.mixer in B.ATTN_MIXERS}
        kv = {n: cache[n]["attn"] for n in carried}
        scanned = {n: {k: a for k, a in c.items() if k != "attn"}
                   for n, c in cache.items()}

        def body(carry, xs):
            h, kv = carry
            layer, blk_params, blk_cache = xs
            kv, new_caches, caps = dict(kv), {}, {}
            for p, spec in enumerate(cfg.pattern):
                n = f"pos{p}"
                c = {**blk_cache[n], "attn": kv[n]} if n in kv \
                    else blk_cache[n]
                h, nc, cap = B.block_decode_step(
                    blk_params[n], shared, cfg, spec, h, c, pos=pos,
                    layer=layer, capture=capture, cross_valid=cross_valid,
                    moe_ffn_fn=self.moe_ffn_fn,
                    moe_layer_fn=self.moe_layer_fn,
                    moe_executor=executor,
                    moe_grouped_fn=self.moe_grouped_fn,
                    moe_router_impl=router_impl,
                    dense_threshold=self.decode_dense_threshold,
                    kv_len=kv_len, attn_backend=backend)
                if n in kv:
                    kv[n] = nc.pop("attn")
                new_caches[n] = nc
                caps[n] = cap
            return (h, kv), (new_caches, caps)

        (x, kv), (new_cache, caps) = jax.lax.scan(
            body, (x, kv),
            (jnp.arange(cfg.num_blocks), params["blocks"], scanned))
        for n in carried:
            new_cache[n]["attn"] = kv[n]
        with jax.named_scope("head"):
            x = apply_norm(cfg.norm, params["final_norm"], x)
            logits = x @ (params["embed"].T if cfg.tie_embeddings
                          else params["lm_head"])
        if capture:
            return logits, new_cache, caps
        return logits, new_cache
