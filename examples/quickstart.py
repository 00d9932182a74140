"""Quickstart: the paper's pipeline end-to-end through the plan API.

Builds a (reduced) GPT2-MoE, profiles token-to-expert routing on the
synthetic corpus, fits the Bayesian expert predictor (Eq. 1-2), plans the
deployment with the registered ODS planner (3 per-method solvers + Alg. 1)
into a serializable ``DeploymentPlan``, round-trips the plan through JSON,
and executes it on the ``SimulatorBackend`` — then compares against the
LambdaML and CPU-cluster baselines.

Run:  PYTHONPATH=src python examples/quickstart.py [--smoke]
(``--smoke`` shrinks the model/corpus for CI.)
"""
import argparse

import numpy as np

from repro.core.predictor import ExpertPredictor
from repro.core.runtime import RuntimeConfig, ServerlessMoERuntime
from repro.device import enable_compile_cache
from repro.plan import DeploymentPlan, Workload

ap = argparse.ArgumentParser()
ap.add_argument("--smoke", action="store_true",
                help="reduced smoke mode (CI): tiny dims, fewer batches")
args = ap.parse_args()
enable_compile_cache()

if args.smoke:
    rc = RuntimeConfig(arch="gpt2-moe", profile_batches=2, learn_batches=1,
                       eval_batches=1, seq_len=32, batch_size=2,
                       d_model_reduced=64, vocab_reduced=512)
else:
    rc = RuntimeConfig(arch="gpt2-moe", profile_batches=4, learn_batches=1,
                       eval_batches=2, seq_len=64, batch_size=4)
rt = ServerlessMoERuntime(rc)
print(f"model: {rt.cfg.name}  ({rt.num_layers} MoE layers x "
      f"{rt.num_experts} experts, top-{rt.top_k})")
print(f"calibrated per-token expert time u_ref = {rt.profile.u_ref_s:.2e} s")

# 1. profile the key-value dataset table (paper §III-B)
table = rt.profile_table()
print(f"profiled {len(table)} key-value entries")

# 2. predict expert selection for a fresh batch
pred = ExpertPredictor(table, top_k=rt.top_k).fit()
batch = rt.learn_batches()[0]
demand = pred.predict_demand(batch)
real = rt.real_demand(batch)
print(f"prediction difference per expert: "
      f"{pred.prediction_difference(demand, real):.2f} tokens")

# 3. plan (registered ODS planner, Alg. 1) -> serializable DeploymentPlan
plan = rt.plan(demand)
print(f"planner={plan.planner!r} v{plan.version}: methods {plan.method} "
      f"beta={plan.beta} chunks={plan.chunk_schedule}")

# 4. the plan is the artifact: JSON round-trip, then execute on a backend
reloaded = DeploymentPlan.from_json(plan.to_json())
backend = rt.simulator_backend()
report = backend.execute(reloaded, Workload(batches=[batch]))
print(f"ours:      ${report.billed_cost:.6f}  "
      f"{report.throughput_tps:.1f} tok/s  (backend={report.backend})")

# 5. baselines
out = rt.evaluate_all()
for k in ("lambdaml", "cpu_cluster"):
    v = out[k]
    print(f"{k:10s} ${v['billed_cost']:.6f}  "
          f"{v['throughput_tps']:.1f} tok/s")
ours = out["serverless_bo"]["billed_cost"]
print(f"saving vs CPU cluster: "
      f"{100 * (1 - ours / out['cpu_cluster']['billed_cost']):.1f}%  "
      f"(paper: >=75.67%)")
print(f"saving vs LambdaML:    "
      f"{100 * (1 - ours / out['lambdaml']['billed_cost']):.1f}%  "
      f"(paper: >=43.41%)")
