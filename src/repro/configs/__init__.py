"""Architecture registry.

Importing this package registers every assigned architecture (plus the
paper's own Bert/GPT2/Bert2Bert MoE conversions) in
``repro.config.ARCH_REGISTRY``. Each module cites its source in brackets.
"""
from repro.configs import (  # noqa: F401
    codeqwen1_5_7b,
    granite_34b,
    qwen3_4b,
    qwen2_moe_a2_7b,
    gemma3_12b,
    llava_next_mistral_7b,
    xlstm_350m,
    granite_moe_3b_a800m,
    zamba2_7b,
    whisper_small,
    paper_bert_moe,
    paper_gpt2_moe,
    paper_bert2bert_moe,
    deepseek_v2_lite,
)

#: The architectures assigned to this paper, in assignment order.
ASSIGNED = (
    "codeqwen1.5-7b",
    "granite-34b",
    "qwen3-4b",
    "qwen2-moe-a2.7b",
    "gemma3-12b",
    "llava-next-mistral-7b",
    "xlstm-350m",
    "granite-moe-3b-a800m",
    "zamba2-7b",
    "whisper-small",
    "deepseek-v2-lite",
)

#: The paper's own evaluation models (converted dense->MoE).
PAPER_MODELS = ("bert-moe", "gpt2-moe", "bert2bert-moe")
