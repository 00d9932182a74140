"""The BO framework (Alg. 2) in detail: acquisition comparison + feedback.

Runs the multi-dimensional eps-greedy BO against single-eps / random / TPE
on the same workload and prints the per-iteration cost trajectory — the
reproduction of the paper's Fig. 13 at example scale. The loop runs
entirely through the plan API: every BO trial predicts demand, plans via
the registered ``Planner``, and executes the resulting ``DeploymentPlan``
on the ``SimulatorBackend``; the winning acquisition's final plan is
produced by ``BOPlanner`` and serialized to JSON.

Run:  PYTHONPATH=src python examples/bo_deployment.py --iters 5
"""
import argparse

from repro.core.runtime import RuntimeConfig, ServerlessMoERuntime
from repro.device import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--arch", default="bert-moe")
    args = ap.parse_args()
    enable_compile_cache()

    rc = RuntimeConfig(arch=args.arch, profile_batches=4, learn_batches=1,
                       eval_batches=1, seq_len=64, batch_size=4,
                       jitter=0.03)
    rt = ServerlessMoERuntime(rc)
    rt.profile_table()
    base = rt.make_eval_fn()(rt.table)
    print(f"no-BO baseline billed cost: ${base.cost:.6f}\n")

    for acq in ("multi_eps", "single_eps", "random", "tpe"):
        res = rt.run_bo(Q=40, max_iters=args.iters, acquisition=acq, seed=3)
        traj = " -> ".join(f"{c:.2e}" for c in res.costs)
        print(f"{acq:12s} best=${res.best_cost:.6f} "
              f"(ratio {res.best_cost / base.cost:.3f})  [{traj}]")

    # Alg. 2 as a Planner: BO-refine the table, then emit the deployment
    # artifact every backend consumes.
    plan = rt.plan_bo(Q=40, max_iters=args.iters, seed=3)
    bo_meta = plan.metadata["bo"]
    print(f"\nBOPlanner -> DeploymentPlan (planner={plan.planner!r}): "
          f"best trial ${bo_meta['best_cost']:.6f} over "
          f"{bo_meta['iterations']} iters; plan JSON is "
          f"{len(plan.to_json())} bytes")


if __name__ == "__main__":
    main()
