"""A whole run of the harness at a tiny size on the CPU (the look for a
chip skipped): sound, it is correct and the float8 control is not; with
the timed path broken underneath in each way a serving cell can break,
``correct`` comes out false."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from chipbench import run as R
from chipbench_tiny import LIMITS, MIX, config

ROOT = Path(R.__file__).resolve().parents[1]
BENCH = {"end_to_end": [{"name": "output_tokens_per_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}
CELL = {"name": "tiny", "chips": 1}


def run_tiny(control=False):
    cfg, mc = config("gpt2-moe")
    return R.run(CELL, cfg, MIX, BENCH, seed=2 ** 33 + 99, seconds=1.0,
                 trace=False, control=control, model_cfg=mc)


def test_sound_run_is_correct_and_the_control_is_not():
    out = run_tiny(control=True)
    assert out["correct"] is True
    assert list(out["checks"])[0] == "gap_p95"
    c = out["checks"]
    limited = [k for k in c if "limit" in c[k] and k != "bad_finish"
               and not k.startswith("control_")]
    assert set(limited) == set(LIMITS)
    for k in limited:
        assert c[k]["value"] <= c[k]["limit"]
        assert c["control_" + k]["value"] > c[k]["limit"]
    assert c["served_tokens_compared"]["value"] >= 24
    assert c["long_tokens_compared"]["value"] >= 8
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_warm_up_leaves_nothing_to_compile_in_the_window():
    """Set-up loads every prefill and decode shape the window reaches; the
    window closes as its last step ends and counts every token served."""
    cfg, mc = config("gpt2-moe")
    vocab = cfg["model"]["vocab_size"]
    counter = R.CompileCounter()
    params, eng = R.build(cfg, 5, mc)
    R.warm_up(eng, MIX, vocab)
    rec = R.serve(eng, MIX, vocab, 5, 1.0, counter, None)
    assert rec["compiles_in_window"] == 0
    assert rec["window_s"] >= 1.0
    assert rec["tokens_in_window"] == sum(
        len(r["times"]) for r in rec["requests"]) > 0


def _broken_decode(monkeypatch, change=None, keep_state=False):
    from repro.serving.engine import ServingEngine

    orig = ServingEngine._decode_impl

    def decode(self, params, toks, cache, *rest):
        logits, new, caps = orig(self, params, toks, cache, *rest)
        return (change(logits) if change else logits,
                cache if keep_state else new, caps)

    monkeypatch.setattr(ServingEngine, "_decode_impl", decode)


def test_state_left_unchanged_fails(monkeypatch):
    """The decode step returns the cache it was given: its writes are
    lost and every later step reads the state from before it."""
    _broken_decode(monkeypatch, keep_state=True)
    assert run_tiny()["correct"] is False


def test_half_the_batch_left_out_fails(monkeypatch):
    """The upper half of the slots never gets its own logits."""
    def half(logits):
        n = logits.shape[0] // 2
        return logits.at[n:].set(0.0)

    _broken_decode(monkeypatch, half)
    assert run_tiny()["correct"] is False


def test_one_slot_altered_fails(monkeypatch):
    """Slot 0 alone decodes the next id after the one the model chose."""
    _broken_decode(monkeypatch,
                   lambda lg: lg.at[0].set(jnp.roll(lg[0], 1, axis=-1)))
    assert run_tiny()["correct"] is False


def test_token_altered_where_produced_fails(monkeypatch):
    """Every decoded token is the next id after the one the model chose."""
    _broken_decode(monkeypatch, lambda lg: jnp.roll(lg, 1, axis=-1))
    assert run_tiny()["correct"] is False


def _bench_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "gpt2moe-chat-backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_no_chip_no_result():
    p = _bench_cmd(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _bench_cmd(tmp_path, env)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_is_an_error():
    assert R.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(R.Refused):
        R.peaks_for("TPU v99 imaginary")
