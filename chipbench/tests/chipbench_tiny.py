"""Tiny versions of the benchmark's configurations, for CPU tests: the
same equations (reference module, norm, positions, activation, tying) at
small widths, with the program's matching model config."""
import dataclasses
import json
from pathlib import Path

CONFIGS = Path(__file__).resolve().parents[1] / "configs"

SMALL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "head_dim": 16,
         "vocab_size": 512, "max_seq_len": 128}
EXPERTS = {"gpt2-moe": {"num_kv_heads": 4, "num_experts": 4, "top_k": 1,
                        "d_expert_ff": 128},
           "granite-moe-3b-a800m": {"num_kv_heads": 2, "num_experts": 8,
                                    "top_k": 2, "d_expert_ff": 32}}

# The limits at this size, from its own readings (gpt2-moe, CPU, seeds
# 2**33 + 99 .. + 104, 72-94 served tokens each): the program's gap_p95
# 0 to 0.0078 and long_gap_p95 0; the float8 control's 0.129 to 0.338 and
# 0.104 to 0.859.
LIMITS = {"gap_p95": 0.03, "long_gap_p95": 0.03}

MIX = {"arrivals": {"kind": "backlog"},
       "prompt": {"grid_min": 4, "grid_max": 24, "grid_n": 6, "median": 10,
                  "sigma": 0.9},
       "output": {"median": 6, "sigma": 0.6, "min": 3, "max": 12},
       "tokens": {"kind": "zipf", "s": 1.1}}


def config(name: str, *, slots: int = 4, max_len: int = 64):
    """(benchmark configuration dict, the program's ModelConfig)."""
    import repro.configs  # noqa: F401
    from repro.config import MoEConfig, get_arch

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg["model"].update(SMALL, **EXPERTS[name])
    m = cfg["model"]
    cfg["serving"] = {"slots": slots, "max_len": max_len, "engine": {}}
    cfg["program"] = {k: v for k, v in cfg.get("program", {}).items()
                      if k != "max_seq_len"}
    cfg["check"] = dict(cfg["check"], sample_requests=12, sample_tokens=24,
                        long_from=18, long_tokens=8,
                        limits=dict(LIMITS))
    mc = dataclasses.replace(
        get_arch(cfg["arch"]), num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], d_ff=m["d_expert_ff"],
        vocab_size=m["vocab_size"], max_seq_len=m["max_seq_len"],
        moe=MoEConfig(num_experts=m["num_experts"], top_k=m["top_k"],
                      d_expert_ff=m["d_expert_ff"]),
        **cfg["program"])
    return cfg, mc


FIXTURE = Path(__file__).resolve().parent / "fixture"


def fixture():
    """The dense-shared-moe fixture (``fixture/``): its configuration dict
    and the program's ModelConfig that serves it."""
    import repro.configs  # noqa: F401
    from repro.config import LayerSpec, MoEConfig, get_arch

    cfg = json.loads((FIXTURE / "dense-shared-moe.json").read_text())
    m = cfg["model"]
    mc = dataclasses.replace(
        get_arch(cfg["arch"]), num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], d_ff=m["d_dense_ff"],
        vocab_size=m["vocab_size"], max_seq_len=m["max_seq_len"],
        pattern=tuple(LayerSpec(*s) for s in m["pattern"]),
        moe=MoEConfig(num_experts=m["num_experts"], top_k=m["top_k"],
                      d_expert_ff=m["d_expert_ff"],
                      num_shared_experts=m["num_shared_experts"],
                      d_shared_ff=m["d_shared_ff"]))
    return cfg, mc
