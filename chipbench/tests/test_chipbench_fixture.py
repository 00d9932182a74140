"""A configuration that only a reference module's own layers and the
counts' keys can describe, added to a copy of the benchmark with new
files alone (``fixture/``: its configuration and reference module, and
a traffic mix): a dense layer, a pattern of two and a shared expert,
served by the program and checked through ``run``. Sound, it is correct;
its float8 control, the same configuration checked with common's
default layers, and the program with its shared expert left out are
not."""
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import counts, traffic
from chipbench import run as R
from chipbench_tiny import FIXTURE, MIX, fixture

ROOT = Path(R.__file__).resolve().parents[1]
NAME = "dense-shared-moe"
SEED = 2 ** 33 + 201


def install(tmp_path, monkeypatch, own_layers=True):
    """The benchmark copied to ``tmp_path`` with the fixture's files
    added; returns the cell, configuration, mix and benchmark as
    ``run.load_cell`` finds them there."""
    bench = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIXTURE / f"{NAME}.json", bench / "configs" / f"{NAME}.json")
    ref = (FIXTURE / f"{NAME}.py").read_text()
    if not own_layers:      # the module without its layers: common's default
        ref += "\ndel layers\n"
    (bench / "reference" / f"{NAME}.py").write_text(ref)
    (bench / "traffic" / "tiny-backlog.json").write_text(json.dumps(MIX))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": NAME, "source": "https://example.org",
                         "file": f"chipbench/configs/{NAME}.json",
                         "reduced": [], "why": "a fixture"})
    b["workloads"].append({"name": "fixture-cell", "config": NAME,
                           "traffic": "tiny-backlog", "chips": 1,
                           "why": "a fixture"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(traffic, "HERE", bench)
    monkeypatch.setattr(R, "BENCH", bench)
    monkeypatch.setattr(R, "ROOT", tmp_path)
    bench_json, cell, cfg, mix = R.load_cell("fixture-cell")
    return cell, cfg, mix, bench_json


def run_fixture(tmp_path, monkeypatch, *, own_layers=True, control=False,
                seed=SEED):
    cell, cfg, mix, bench = install(tmp_path, monkeypatch, own_layers)
    return R.run(cell, cfg, mix, bench, seed=seed, seconds=1.0, trace=False,
                 control=control, model_cfg=fixture()[1])


def test_fixture_is_correct_and_its_control_is_not(tmp_path, monkeypatch):
    out = run_fixture(tmp_path, monkeypatch, control=True)
    c = out["checks"]
    assert out["correct"] is True
    for k in ("gap_p95", "long_gap_p95"):
        assert c[k]["value"] <= c[k]["limit"]
        assert c["control_" + k]["value"] > c[k]["limit"]
    assert c["served_tokens_compared"]["value"] >= 24


def test_fixture_with_the_default_layers_is_not_correct(tmp_path,
                                                        monkeypatch):
    """common's default layers run the dense and the routed layers, but
    know nothing of the shared expert."""
    assert run_fixture(tmp_path, monkeypatch,
                       own_layers=False)["correct"] is False


def test_shared_expert_left_out_fails(tmp_path, monkeypatch):
    """The program's MoE layer returns the routed experts alone."""
    from repro.models import moe

    monkeypatch.setattr(moe, "mlp_forward",
                        lambda p, x, act: jnp.zeros_like(x))
    assert run_fixture(tmp_path, monkeypatch)["correct"] is False


def test_fixture_keys_are_compared_with_the_program(tmp_path, monkeypatch):
    """Every ``program_keys`` key is checked against the program's config:
    a program without the shared expert is refused before it serves."""
    import dataclasses

    cell, cfg, mix, bench = install(tmp_path, monkeypatch)
    mc = fixture()[1]
    assert R.program_field(mc, "pattern") == cfg["model"]["pattern"]
    no_shared = dataclasses.replace(
        mc, moe=dataclasses.replace(mc.moe, num_shared_experts=0))
    with pytest.raises(R.Refused, match="num_shared_experts"):
        R.build(cfg, 1, no_shared)


def test_fixture_counts_by_hand():
    m = json.loads((FIXTURE / f"{NAME}.json").read_text())["model"]
    # attention: q 64 x 64, k and v 64 x 32 (2 KV heads of 16), o 64 x 64
    assert counts.attn_params(m) == 64 * 64 + 2 * 64 * 32 + 64 * 64 == 12_288
    # 2 dense layers of SwiGLU 64 -> 96, 2 MoE layers: router 64 x 8, two
    # SwiGLU experts of 32 and the shared SwiGLU expert of 48; the LM head
    dense, shared, expert = 3 * 64 * 96, 3 * 64 * 48, 3 * 64 * 32
    active = (4 * 12_288 + 2 * dense + 2 * (64 * 8 + 2 * expert + shared)
              + 64 * 512)
    assert counts.active_params(m) == active == 162_816
    # attention's QK and PV over 4 layers, 4 heads of 16
    assert counts.token_flops(m, 10) == 2 * active + 4 * 4 * 4 * 16 * 10
    # a decode step reads every weight but the experts not touched; the
    # cache holds K and V of 2 heads of 16 in 4 layers, in bf16
    c = counts.decode_step(m, [10, 20], touched=[0, 3, 0, 5])
    weights = (4 * 12_288 + 2 * dense + 2 * (64 * 8 + shared) + 8 * expert
               + 64 * 512)
    assert c["bytes"] == 2 * weights + 30 * 4 * 2 * 2 * 16 * 2
    # a prompt of 3 tokens touches at most 3 x 2 experts in each MoE layer
    p = counts.prefill(m, 3)
    assert p["bytes"] == 2 * (weights - 8 * expert + 2 * 6 * expert) \
        + 3 * 4 * 2 * 2 * 16 * 2
