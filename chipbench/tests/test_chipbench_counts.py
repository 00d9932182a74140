"""counts.py against a count by hand for gpt2-moe."""
import json
from pathlib import Path

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
