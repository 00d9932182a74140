"""Configurations, traffic mixes and metrics are found by file name: every
name in BENCHMARK.json has its file, and a file added under the
benchmark's directory is found without an edit anywhere."""
import json
import shutil
from pathlib import Path

import pytest

from chipbench import run as R
from chipbench import traffic

ROOT = Path(R.__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_has_its_file():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (ROOT / "chipbench" / "reference" / f"{c['name']}.py").is_file()
    for w in BENCH["workloads"]:
        traffic.load(w["traffic"])
        bench, cell, cfg, mix = R.load_cell(w["name"])
        assert cell is not None and cfg["name"] == w["config"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = R.load_module(ROOT / "chipbench" / "metrics" / f"{m['name']}.py",
                            "t_" + m["name"].replace(".", "_"))
        assert callable(mod.read)


def test_cell_metrics_follow_their_workloads():
    for w in BENCH["workloads"]:
        e2e = {m["name"] for m in R.cell_metrics(BENCH, w["name"],
                                                 "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert R.cell_metrics(BENCH, w["name"], "per_layer")


def test_added_files_are_found(tmp_path, monkeypatch):
    bench = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "traffic" / "extra-mix.json").write_text(json.dumps(
        dict(traffic.load("chat-backlog"), about="an added mix")))
    (bench / "metrics" / "extra_metric.py").write_text(
        "def read(rec):\n    return rec['tokens_in_window'] * 2\n")
    (bench / "metrics" / "silent_metric.py").write_text(
        "def read(rec):\n    return None\n")
    cfg = json.loads((bench / "configs" / "gpt2-moe.json").read_text())
    cfg["name"] = "extra-config"
    (bench / "configs" / "extra-config.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "reference" / "gpt2-moe.py",
                bench / "reference" / "extra-config.py")
    b = dict(BENCH)
    b["configs"] = BENCH["configs"] + [{
        "name": "extra-config", "source": "https://example.org",
        "file": "chipbench/configs/extra-config.json", "reduced": [],
        "why": "added"}]
    b["workloads"] = BENCH["workloads"] + [{
        "name": "extra-cell", "config": "extra-config",
        "traffic": "extra-mix", "chips": 1, "why": "added"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(traffic, "HERE", bench)
    monkeypatch.setattr(R, "BENCH", bench)
    monkeypatch.setattr(R, "ROOT", tmp_path)

    _, cell, cfg, mix = R.load_cell("extra-cell")
    assert cfg["name"] == "extra-config" and mix["about"] == "an added mix"
    got = R.read_metrics([{"name": "extra_metric", "unit": "x"},
                          {"name": "silent_metric", "unit": "x"}],
                         {"tokens_in_window": 21})
    assert got == {"extra_metric": {"value": 42.0, "unit": "x"}}
    with pytest.raises(R.Refused):
        R.read_metrics([{"name": "missing_metric", "unit": "x"}], {})
