"""Configuration system for the serverless-MoE reproduction framework.

Everything in the framework is driven by three dataclass families:

* :class:`ModelConfig`   -- architecture definition (the model zoo consumes it).
* :class:`ShapeConfig`   -- the assigned input shapes (train_4k, prefill_32k, ...).
* :class:`MeshConfig`    -- device mesh geometry for the dry-run / launcher.

Architectures register themselves in :data:`ARCH_REGISTRY` via
:func:`register_arch`; ``repro.configs`` imports every config module so that
``get_arch("qwen3-4b")`` works after ``import repro.configs``.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Layer / block specification
# ---------------------------------------------------------------------------

#: Mixer kinds understood by ``repro.models.blocks``.
MIXER_KINDS = (
    "attn",          # full (global) causal self-attention
    "swa",           # sliding-window causal self-attention
    "mamba2",        # Mamba2 SSD block
    "mlstm",         # xLSTM matrix-memory LSTM block
    "slstm",         # xLSTM scalar-memory LSTM block (strictly sequential)
    "shared_attn",   # zamba-style globally shared attention block
    "mla",           # multi-head latent attention (DeepSeek-V2)
)

#: Feed-forward kinds.
FFN_KINDS = ("dense", "moe", "none")


@dataclass(frozen=True)
class LayerSpec:
    """One transformer block = a sequence mixer + a feed-forward stage."""

    mixer: str = "attn"
    ffn: str = "dense"

    def __post_init__(self) -> None:
        if self.mixer not in MIXER_KINDS:
            raise ValueError(f"unknown mixer kind {self.mixer!r}")
        if self.ffn not in FFN_KINDS:
            raise ValueError(f"unknown ffn kind {self.ffn!r}")


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-Experts settings for layers whose ``ffn == 'moe'``."""

    num_experts: int
    top_k: int
    d_expert_ff: int
    num_shared_experts: int = 0
    d_shared_ff: int = 0                  # per shared expert; 0 -> d_expert_ff
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01         # load-balance auxiliary loss
    router_z_coef: float = 1e-3
    # dispatch implementation: "dense" (local einsum-free sort/scatter),
    # "expert_parallel" (all_to_all), "expert_parallel_pipelined" (beta chunks)
    dispatch: str = "dense"
    pipeline_degree: int = 1              # beta, used by the pipelined dispatch
    # top-k weights renormalized over the k (False: the raw softmax probs)
    norm_topk_prob: bool = True
    # experts 0 .. experts_held-1 are computed here, of the router's
    # num_experts (0 -> all): one chip's share under expert parallelism
    experts_held: int = 0

    def __post_init__(self) -> None:
        if not 0 <= self.experts_held <= self.num_experts:
            raise ValueError(f"experts_held={self.experts_held} outside "
                             f"0..{self.num_experts}")

    @property
    def shared_ff(self) -> int:
        return self.d_shared_ff or self.d_expert_ff

    @property
    def held(self) -> int:
        """Experts whose weights this layer holds and computes."""
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention widths (DeepSeek-V2, no query LoRA).

    Keys and values are decompressed per head from a ``kv_lora_rank``
    latent; a ``qk_rope_head_dim`` rotary key is shared by the heads. The
    decode cache holds the normalized latent and the rotated key."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def latent_dim(self) -> int:
        """Cached values a token a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (Peng et al., 2023) as DeepSeek-V2 states it."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / xLSTM settings for the relevant mixer kinds."""

    state_size: int = 64       # N, per-head SSM state (mamba2)
    head_dim: int = 64         # P, mamba2 head dim
    expand: int = 2            # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256      # SSD chunk length
    # xLSTM specifics
    mlstm_heads: int = 4
    slstm_heads: int = 4
    proj_factor: float = 2.0   # mLSTM up-projection factor


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for encoder-decoder models (whisper)."""

    num_layers: int
    num_heads: int
    d_ff: int
    source_len: int = 1500     # number of frames/patches delivered by the stub


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    """Full architecture description.

    ``pattern`` is the repeating unit of layer specs; ``num_layers`` must be
    ``first_dense_layers + len(pattern) * num_blocks``. Stacks are scanned
    over ``num_blocks`` so compile time is O(len(pattern)), not
    O(num_layers). The ``first_dense_layers`` leading layers (DeepSeek's
    ``first_k_dense_replace``) pair the pattern's first mixer with a dense
    feed-forward of ``d_ff`` and sit outside the scan.
    """

    name: str
    arch_type: str                       # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    pattern: Tuple[LayerSpec, ...] = (LayerSpec(),)
    head_dim: int = 0                    # 0 -> d_model // num_heads
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    mla: Optional[MLAConfig] = None
    first_dense_layers: int = 0
    qk_norm: bool = False
    sliding_window: int = 0              # 0 -> disabled; used by "swa" mixers
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnScaling] = None   # the mla mixer only
    pos_embed: str = "rope"              # rope | learned | none
    norm: str = "rmsnorm"                # rmsnorm | layernorm
    activation: str = "swiglu"           # swiglu | gelu
    tie_embeddings: bool = False
    causal: bool = True                  # False -> bidirectional encoder (bert)
    max_seq_len: int = 32_768
    frontend: str = "none"               # none | audio_stub | vision_stub
    frontend_tokens: int = 0             # patches/frames prepended by the stub
    dtype: str = "bfloat16"
    # citation for the architecture source (paper / model card)
    source: str = ""
    # whether this arch can serve a 500k-token context (sub-quadratic path)
    supports_long_context: bool = False
    notes: str = ""

    # ------------------------------------------------------------------ derived
    def __post_init__(self) -> None:
        if (self.num_layers - self.first_dense_layers) % len(self.pattern):
            raise ValueError(
                f"{self.name}: num_layers={self.num_layers} less "
                f"{self.first_dense_layers} leading dense layers not "
                f"divisible by pattern length {len(self.pattern)}"
            )
        mixers = {s.mixer for s in self.pattern}
        if "mla" in mixers:
            if self.mla is None:
                raise ValueError(f"{self.name}: mla mixer without MLAConfig")
            qk = self.mla.qk_nope_head_dim + self.mla.qk_rope_head_dim
            if self.resolved_head_dim != qk:
                raise ValueError(f"{self.name}: head_dim must be the mla "
                                 f"query/key width {qk}")
        if self.first_dense_layers and (
                self.pattern[0].mixer not in ("attn", "swa", "mla")
                or self.encoder is not None):
            raise ValueError(f"{self.name}: leading dense layers need a "
                             f"decoder whose first mixer is attention")
        if self.rope_scaling is not None and mixers != {"mla"}:
            raise ValueError(f"{self.name}: rope scaling is implemented for "
                             f"the mla mixer only")
        for spec in self.pattern:
            if spec.ffn == "moe" and self.moe is None:
                raise ValueError(f"{self.name}: moe layer without MoEConfig")
            if spec.mixer in ("mamba2", "mlstm", "slstm") and self.ssm is None:
                raise ValueError(f"{self.name}: ssm mixer without SSMConfig")
            if spec.mixer == "swa" and self.sliding_window <= 0:
                raise ValueError(f"{self.name}: swa mixer without sliding_window")

    @property
    def num_blocks(self) -> int:
        return (self.num_layers - self.first_dense_layers) // len(self.pattern)

    @property
    def lead_spec(self) -> LayerSpec:
        """The leading dense layers' spec: the pattern's first mixer."""
        return LayerSpec(self.pattern[0].mixer, "dense")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so it shards over 16-way axes."""
        return _round_up(self.vocab_size, 256)

    @property
    def has_moe(self) -> bool:
        return any(s.ffn == "moe" for s in self.pattern)

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder is not None

    @property
    def has_attention(self) -> bool:
        return any(s.mixer in ("attn", "swa", "shared_attn", "mla")
                   for s in self.pattern)

    def padded_experts(self, multiple: int) -> int:
        """Experts padded up to ``multiple`` for expert-parallel sharding."""
        assert self.moe is not None
        return _round_up(self.moe.num_experts, multiple)

    # -------------------------------------------------------------- accounting
    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, hd = self.d_model, self.resolved_head_dim
        nh, nkv = self.num_heads, self.num_kv_heads
        total = self.padded_vocab * d                      # embedding
        if not self.tie_embeddings:
            total += self.padded_vocab * d                 # lm head
        if self.pos_embed == "learned":
            total += self.max_seq_len * d

        def attn_params() -> int:
            return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 2 * d  # q,k,v,o + norms

        def mla_params() -> int:
            a = self.mla
            assert a is not None
            return (d * nh * hd + d * a.latent_dim + a.kv_lora_rank
                    + a.kv_lora_rank * nh * (a.qk_nope_head_dim + a.v_head_dim)
                    + nh * a.v_head_dim * d + 2 * d)

        def ffn_params(ff: int) -> int:
            if self.activation == "swiglu":
                return 3 * d * ff
            return 2 * d * ff

        def mixer_params(kind: str) -> int:
            if kind in ("attn", "swa", "shared_attn"):
                return attn_params()
            if kind == "mla":
                return mla_params()
            s = self.ssm
            assert s is not None
            if kind == "mamba2":
                d_in = s.expand * d
                nheads = d_in // s.head_dim
                # in_proj (z,x,B,C,dt) + conv + out_proj + A,D
                return (d * (2 * d_in + 2 * s.state_size * nheads + nheads)
                        + s.conv_width * (d_in + 2 * s.state_size * nheads)
                        + d_in * d + 2 * nheads)
            if kind == "mlstm":
                d_in = int(s.proj_factor * d)
                return d * 2 * d_in + 3 * d_in * d_in + d_in * d + 3 * d_in
            if kind == "slstm":
                return 4 * d * d + 4 * d * d + d * d    # gates + recurrent + out
            raise ValueError(kind)

        shared_attn_counted = False
        per_unit = 0
        for spec in self.pattern:
            if spec.mixer == "shared_attn":
                if not shared_attn_counted:
                    total += mixer_params("attn")          # shared once globally
                    shared_attn_counted = True
            else:
                per_unit += mixer_params(spec.mixer)
            if spec.ffn == "dense":
                per_unit += ffn_params(self.d_ff)
            elif spec.ffn == "moe":
                m = self.moe
                assert m is not None
                per_unit += d * m.num_experts                       # router
                per_unit += m.num_experts * ffn_params(m.d_expert_ff)
                per_unit += m.num_shared_experts * ffn_params(m.shared_ff)
            per_unit += 2 * d                                        # block norms
        total += per_unit * self.num_blocks
        total += self.first_dense_layers * (
            mixer_params(self.lead_spec.mixer) + ffn_params(self.d_ff) + 2 * d)

        if self.encoder is not None:
            e = self.encoder
            enc_layer = attn_params() + ffn_params(e.d_ff) + 2 * d
            total += e.num_layers * enc_layer
            # cross attention in every decoder layer
            total += self.num_layers * attn_params()
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only top-k + shared experts)."""
        if not self.has_moe:
            return self.param_count()
        m = self.moe
        assert m is not None
        d = self.d_model

        def ffn_params(ff: int) -> int:
            return (3 if self.activation == "swiglu" else 2) * d * ff

        inactive = 0
        for spec in self.pattern:
            if spec.ffn == "moe":
                inactive += (m.num_experts - m.top_k) * ffn_params(m.d_expert_ff)
        return self.param_count() - inactive * self.num_blocks


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Mesh geometry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    @property
    def model_size(self) -> int:
        return self.shape[self.axes.index("model")]

    @property
    def data_size(self) -> int:
        n = self.shape[self.axes.index("data")]
        if "pod" in self.axes:
            n *= self.shape[self.axes.index("pod")]
        return n


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {}


def register_arch(name: str):
    def deco(fn: Callable[[], ModelConfig]) -> Callable[[], ModelConfig]:
        ARCH_REGISTRY[name] = fn
        return fn
    return deco


def get_arch(name: str) -> ModelConfig:
    import repro.configs  # noqa: F401  (populates the registry)

    if name not in ARCH_REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; known: {sorted(ARCH_REGISTRY)}")
    return ARCH_REGISTRY[name]()


def list_archs() -> Sequence[str]:
    import repro.configs  # noqa: F401

    return sorted(ARCH_REGISTRY)


def reduced_config(cfg: ModelConfig, *, num_blocks: int = 2,
                   d_model: int = 256, max_experts: int = 4,
                   vocab: int = 512) -> ModelConfig:
    """Smoke-test variant: same family, tiny dims (<=2 unit-blocks, d_model<=512)."""
    scale = d_model / cfg.d_model
    heads = max(2, min(cfg.num_heads, 4))
    kv = max(1, min(cfg.num_kv_heads, heads))
    hd = max(16, d_model // heads)
    moe = cfg.moe
    if moe is not None:
        n_exp = min(moe.num_experts, max_experts)
        moe = dataclasses.replace(
            moe,
            num_experts=n_exp,
            experts_held=(max(1, moe.experts_held * n_exp // moe.num_experts)
                          if moe.experts_held else 0),
            top_k=min(moe.top_k, 2),
            d_expert_ff=max(32, int(moe.d_expert_ff * scale)),
            num_shared_experts=min(moe.num_shared_experts, 1),
            d_shared_ff=max(32, int(moe.shared_ff * scale)),
        )
    ssm = cfg.ssm
    if ssm is not None:
        ssm = dataclasses.replace(
            ssm, state_size=min(ssm.state_size, 16), head_dim=32,
            chunk_size=64, mlstm_heads=2, slstm_heads=2)
    enc = cfg.encoder
    if enc is not None:
        enc = dataclasses.replace(enc, num_layers=2, num_heads=heads,
                                  d_ff=2 * d_model, source_len=16)
    mla = cfg.mla
    if mla is not None:
        mla = MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                        qk_rope_head_dim=16, v_head_dim=32)
        kv, hd = heads, 48
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=cfg.first_dense_layers + num_blocks * len(cfg.pattern),
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=hd,
        d_ff=max(64, int(cfg.d_ff * scale)) if cfg.d_ff else 0,
        vocab_size=vocab,
        moe=moe,
        ssm=ssm,
        encoder=enc,
        mla=mla,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        max_seq_len=256,
        frontend_tokens=min(cfg.frontend_tokens, 16),
        dtype="float32",
    )
