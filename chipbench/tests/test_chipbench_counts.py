"""counts.py against a count by hand for gpt2-moe and for a layer that
states its own keys (latent attention, a dense layer, shared experts, a
held share of the experts), and against the counts both configuration
files had before a layer could state them."""
import json
from pathlib import Path

import pytest

from chipbench import counts

CFG = json.loads((Path(__file__).parents[1] / "configs" / "gpt2-moe.json")
                 .read_text())["model"]


def test_gpt2_moe_by_hand():
    m = CFG
    # attention: q, k, v, o of 768 x 768 (12 heads of 64, no GQA)
    assert counts.attn_params(m) == 4 * 768 * 768 == 2_359_296
    # one expert: W_in 768 x 3072 and W_out 3072 x 768
    assert counts.expert_params(m) == 2 * 768 * 3072 == 4_718_592
    # per layer: attention, router 768 x 4, one expert (top-1); 12 layers
    # and the LM head 768 x 50257
    assert counts.active_params(m) == 12 * (2_359_296 + 3_072 + 4_718_592) \
        + 768 * 50257 == 123_568_896
    # a token against 100 keys: 2 x active + QK and PV, 12 layers x 12
    # heads x 64
    assert counts.token_flops(m, 100) == 2 * 123_568_896 + 4 * 12 * 12 * 64 \
        * 100 == 250_824_192


def test_gpt2_moe_decode_step_by_hand():
    c = counts.decode_step(CFG, [100, 200])
    assert c["flops"] == 250_824_192 + 254_510_592
    # bf16: every layer's attention and router weights, all 4 experts of
    # every layer (not told which were touched), the LM head, and K and V
    # of 300 rows: 2 x 12 layers x 12 heads x 64 x 2 bytes a row
    weights = 12 * (2_359_296 + 3_072) + 12 * 4 * 4_718_592 + 768 * 50257
    assert c["bytes"] == 2 * weights + 300 * 36_864
    touched = counts.decode_step(CFG, [100, 200], touched=[1] * 12)
    assert c["bytes"] - touched["bytes"] == 2 * 12 * 3 * 4_718_592


def test_roofline_bound_names_the_larger_time():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = counts.least_seconds({"flops": 197e9, "bytes": 819e6}, peak)
    assert r["bound"] == "flops" and abs(r["seconds"] - 1e-3) < 1e-12
    r = counts.least_seconds({"flops": 1.0, "bytes": 819e9}, peak)
    assert r["bound"] == "bytes" and abs(r["seconds"] - 1.0) < 1e-12


# The counts both configuration files gave before a configuration
# could state its own layer's keys (context lengths 1, 7, 128, 513, 1024):
# token_flops; prefill (flops, bytes); decode_step over [1, 129, 1024],
# then over the five lengths with 3 experts touched in every layer.
LENGTHS = [1, 7, 128, 513, 1024]
BEFORE = {
    "gpt2-moe": {
        "token_flops": [247174656, 247395840, 251856384, 266049024,
                        284886528],
        "prefill": [(247174656, 247174656), (1730996736, 587134464),
                    (31937986560, 591595008), (131641873920, 605787648),
                    (272415326208, 624625152)],
        "decode": [(783954432, 629417472), (1297362432, 535303680)]},
    "granite-moe-3b-a800m": {
        "token_flops": [1765745664, 1766925312, 1790714880, 1866408960,
                        1966875648],
        "prefill": [(1765745664, 1765614592), (12364348416, 6597846016),
                    (227613474816, 6605775872), (931647661056, 6631007232),
                    (1911102111744, 6664496128)],
        "decode": [(5523532800, 6673015808), (9156670464, 1120216064)]},
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_counts_of_both_files_are_unchanged(name):
    m = json.loads((Path(__file__).parents[1] / "configs" / f"{name}.json")
                   .read_text())["model"]
    want = BEFORE[name]
    assert [counts.token_flops(m, n) for n in LENGTHS] == want["token_flops"]
    assert [(counts.prefill(m, n)["flops"], counts.prefill(m, n)["bytes"])
            for n in LENGTHS] == want["prefill"]
    steps = [counts.decode_step(m, [1, 129, 1024]),
             counts.decode_step(m, LENGTHS,
                                touched=[3] * m["num_layers"])]
    assert [(c["flops"], c["bytes"]) for c in steps] == want["decode"]


# DeepSeek-V2-Lite's model as its file would state it
# (huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json), on a chip
# that holds 16 of its 64 routed experts.
MLA = {"num_layers": 27, "d_model": 2048, "num_heads": 16,
       "num_kv_heads": 16, "head_dim": 192, "d_expert_ff": 1408,
       "num_experts": 64, "top_k": 6, "vocab_size": 102400, "act": "swiglu",
       "dtype": "bfloat16",
       # q 2048 x 16·192; kv_a 2048 x (512 + 64); kv_b 512 x 16·(128 + 128);
       # o 16·128 x 2048
       "attn_params": 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048,
       "cached_per_token": 512 + 64,
       # absorbed: QK over 512 latent + 64 rope, PV over 512, 16 heads
       "attn_flops_per_pair": 2 * 16 * 576 + 2 * 16 * 512,
       "dense_layers": 1, "d_dense_ff": 10944,
       "num_shared_experts": 2, "d_shared_ff": 1408,
       "experts_held": 16}


def test_latent_attention_and_a_held_share_by_hand():
    m = MLA
    assert counts.attn_params(m) == 13_762_560
    expert = 3 * 2048 * 1408                       # 8,650,752
    # 26 MoE layers: router 2048 x 64, 6 x 16/64 = 1.5 routed experts on
    # this chip, 2 shared; 1 dense layer of 10944; the untied LM head
    per_moe = 2048 * 64 + 1.5 * expert + 2 * expert
    active = 27 * 13_762_560 + 26 * per_moe + 3 * 2048 * 10944 \
        + 2048 * 102400
    assert counts.active_params(m) == active == 1_439_170_560
    # 34,816 operations a query-key pair in each of 27 layers
    assert counts.token_flops(m, 100) == 2 * active + 27 * 34_816 * 100
    # 576 cached elements a token in each layer, bf16
    assert counts.kv_bytes(m, 100) == 27 * 576 * 100 * 2 == 3_110_400
    # a decode step reads the 16 experts held in each of 26 MoE layers
    c = counts.decode_step(m, [100])
    weights = (27 * 13_762_560 + 26 * 2048 * 64 + 3 * 2048 * 10944
               + 26 * 2 * expert + 26 * 16 * expert + 2048 * 102400)
    assert c["bytes"] == 2 * weights + 3_110_400
    # a prompt of 2 tokens touches at most 2 x 6 of the 16 held
    assert counts.prefill(m, 2)["bytes"] == \
        2 * (weights - 26 * 4 * expert) + 2 * 27 * 576 * 2
