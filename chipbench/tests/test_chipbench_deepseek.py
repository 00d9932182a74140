"""deepseek-v2-lite's files served and checked through ``run``, at a tiny
size on the CPU: the configuration file with its widths cut down (latent
attention, YaRN rope, a leading dense layer, an unrenormalized top-2
over 8 experts of which 4 are held, two shared experts), in a copy of
the benchmark with a tiny traffic mix and cell added. Sound, it is
correct, and its float8 control is not; each planted fault makes it
incorrect."""
import dataclasses
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import traffic
from chipbench import run as R
from chipbench_tiny import MIX

ROOT = Path(R.__file__).resolve().parents[1]
NAME = "deepseek-v2-lite"
SEED = 2 ** 33 + 301

TINY = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "d_expert_ff": 64,
        "num_experts": 8, "top_k": 2, "experts_held": 4, "d_shared_ff": 16,
        "d_dense_ff": 96, "vocab_size": 512, "max_seq_len": 128}

# CPU readings at this size (run.run, 1 s windows, near_tie 0.1), as
# (gap_p95, long_gap_p95): the program's largest over seeds 2**33 + 301
# .. 306 (66-91 served tokens each) 0.0 and 0.0; the float8 control's
# smallest 0.146 and 0.238. Over seeds + 301 .. 303 each fault crosses
# both limits on every seed: renormalized weights at least 0.090 and
# 0.033; no mscale 1.21 and 1.35; k_pe cached before rope 1.00 and 0.99;
# every expert computed 0.238 and 0.422.
LIMITS = {"gap_p95": 0.03, "long_gap_p95": 0.03}


def tiny_config():
    """(configuration dict, the program's ModelConfig) at ``TINY``."""
    import repro.configs  # noqa: F401
    from repro.config import MLAConfig, get_arch

    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{NAME}.json")
                     .read_text())
    cfg["model"].update(TINY)
    cfg["serving"] = {"slots": 4, "max_len": 64, "engine": {}}
    cfg["check"] = {"limits": dict(LIMITS), "near_tie": 0.1, "max_flips": 2,
                    "sample_requests": 12, "sample_tokens": 24,
                    "long_from": 18, "long_tokens": 8}
    m, base = cfg["model"], get_arch(NAME)
    mc = dataclasses.replace(
        base, num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_dense_ff"],
        vocab_size=m["vocab_size"], max_seq_len=m["max_seq_len"],
        mla=MLAConfig(m["kv_lora_rank"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, num_experts=m["num_experts"], top_k=m["top_k"],
            d_expert_ff=m["d_expert_ff"], d_shared_ff=m["d_shared_ff"],
            experts_held=m["experts_held"]))
    return cfg, mc


def run_tiny(tmp_path, monkeypatch, *, control=False, cfg=None, mc=None):
    """``run.run`` of the tiny configuration in a copy of the benchmark
    to which a tiny traffic mix and cell are added."""
    bench = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny_cfg, tiny_mc = tiny_config()
    cfg, mc = cfg or tiny_cfg, mc or tiny_mc
    (bench / "configs" / f"{NAME}.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-backlog.json").write_text(json.dumps(MIX))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny-cell", "config": NAME,
                           "traffic": "tiny-backlog", "chips": 1,
                           "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(traffic, "HERE", bench)
    monkeypatch.setattr(R, "BENCH", bench)
    monkeypatch.setattr(R, "ROOT", tmp_path)
    bench_json, cell, cfg, mix = R.load_cell("tiny-cell")
    return R.run(cell, cfg, mix, bench_json, seed=SEED, seconds=1.0,
                 trace=False, control=control, model_cfg=mc)


def test_tiny_deepseek_is_correct_and_its_control_is_not(tmp_path,
                                                         monkeypatch):
    out = run_tiny(tmp_path, monkeypatch, control=True)
    c = out["checks"]
    assert out["correct"] is True
    for k in LIMITS:
        assert c[k]["value"] <= c[k]["limit"]
        assert c["control_" + k]["value"] > c[k]["limit"]
    assert c["served_tokens_compared"]["value"] >= 24


def _renormalized(monkeypatch):
    """The top-k weights renormalized over the k."""
    from repro.models import moe

    inner = moe.gate_topk
    monkeypatch.setattr(moe, "gate_topk", lambda probs, m: inner(
        probs, dataclasses.replace(m, norm_topk_prob=True)))


def _no_mscale(monkeypatch):
    """The softmax scale without YaRN's mscale(40, 0.707) squared."""
    from repro.models import attention

    monkeypatch.setattr(attention, "yarn_mscale", lambda f, m: 1.0)


def _k_pe_before_rope(monkeypatch):
    """Prefill caches the shared rope key before its rotation."""
    from repro.models import blocks

    inner = blocks.mla_forward

    def mla_forward(params, cfg, x, **kw):
        y, cache, argmax = inner(params, cfg, x, **kw)
        raw = (x @ params["wkv_a"])[..., cfg.mla.kv_lora_rank:]
        return y, dict(cache, k_pe=raw), argmax

    monkeypatch.setattr(blocks, "mla_forward", mla_forward)


FAULTS = {"renormalized": _renormalized, "no_mscale": _no_mscale,
          "k_pe_before_rope": _k_pe_before_rope}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["every_expert"])
def test_a_planted_fault_is_not_correct(fault, tmp_path, monkeypatch):
    cfg, mc = tiny_config()
    if fault == "every_expert":
        # the program computes all 8 experts in place of its share of 4;
        # the key check would refuse it first, so it is not asked
        mc = dataclasses.replace(mc, moe=dataclasses.replace(
            mc.moe, experts_held=0))
        del cfg["program_keys"]["experts_held"]
    else:
        FAULTS[fault](monkeypatch)
    out = run_tiny(tmp_path, monkeypatch, cfg=cfg, mc=mc)
    assert out["correct"] is False
    c = out["checks"]
    assert any(c[k]["value"] > lim for k, lim in LIMITS.items())


def test_the_share_is_checked_against_the_program(tmp_path, monkeypatch):
    """A program that holds every expert is refused before it serves."""
    cfg, mc = tiny_config()
    every = dataclasses.replace(mc, moe=dataclasses.replace(
        mc.moe, experts_held=0))
    with pytest.raises(R.Refused, match="experts_held"):
        R.build(cfg, 1, every)
    no_yarn = dataclasses.replace(mc, rope_scaling=dataclasses.replace(
        mc.rope_scaling, mscale_all_dim=1.0))
    with pytest.raises(R.Refused, match="rope_mscale_all_dim"):
        R.build(cfg, 1, no_yarn)


def test_the_counts_keys_are_the_counts_tests():
    """The file's counts keys are those the counts' own test states by
    hand for this model."""
    from test_chipbench_counts import MLA

    m = json.loads((ROOT / "chipbench" / "configs" / f"{NAME}.json")
                   .read_text())["model"]
    assert {k: m[k] for k in MLA} == MLA
    assert jnp.dtype(m["dtype"]) == jnp.bfloat16
