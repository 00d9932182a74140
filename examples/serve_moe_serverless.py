"""End-to-end driver: BO-optimized serverless deployment + live serving.

The paper's kind is INFERENCE SERVING, so this is the required end-to-end
example, now phrased entirely in the plan API:

1. ``BOPlanner`` (Alg. 2 behind the ``Planner`` protocol) learns the
   key-value table offline and emits a serializable ``DeploymentPlan``;
2. the SAME plan object is executed on both pluggable backends —
   ``SimulatorBackend`` (predicted-demand billing) and ``ServingBackend``
   (the continuous-batching engine serves real requests in the plan's
   chunked scatter-gather rounds, and the measured routing is billed
   under the plan's comm methods) — with an ``OnlinePredictor`` attached
   to the engine, so every decode step emits speculative per-layer
   prewarm hints and reports the live hit rate;
3. the runtime re-plans from the live telemetry and prints the structured
   plan diff the re-plan emitted;
4. the recorded session is replayed as a trace on the fault-injecting
   discrete-event simulator (cold-start storm) to show what the SAME
   traffic would have cost on a misbehaving platform — once reactively
   and once with the online predictor driving speculative pre-warming
   (cold starts convert to prewarm hits, mispredictions bill wasted
   keep-alive GB-seconds).

Run:  PYTHONPATH=src python examples/serve_moe_serverless.py [--requests 6]
"""
import argparse

import numpy as np

from repro.core.runtime import RuntimeConfig, ServerlessMoERuntime
from repro.core.simulator import FaultProfile
from repro.device import enable_compile_cache
from repro.plan import DeploymentPlan, Workload
from repro.serving import ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--bo-iters", type=int, default=4)
    ap.add_argument("--arch", default="gpt2-moe")
    args = ap.parse_args()
    enable_compile_cache()

    rc = RuntimeConfig(arch=args.arch, profile_batches=4, learn_batches=1,
                       eval_batches=1, seq_len=64, batch_size=4)
    rt = ServerlessMoERuntime(rc)

    # --- plan the deployment with the BO planner (offline) ---------------
    plan = rt.plan_bo(Q=40, max_iters=args.bo_iters, seed=0)
    bo = plan.metadata["bo"]
    print(f"BO: {bo['iterations']} iterations, best billed cost "
          f"${bo['best_cost']:.6f} (converged={bo['converged']})")
    print(f"plan: planner={plan.planner!r} methods {plan.method} "
          f"chunks {plan.chunk_schedule}")
    plan = DeploymentPlan.from_json(plan.to_json())   # the wire artifact

    # --- build the live workload -----------------------------------------
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, rt.cfg.vocab_size,
                            size=int(rng.integers(8, 17)))
               for _ in range(args.requests)]
    workload = Workload(batches=prompts, max_new_tokens=8)

    # --- execute the SAME plan on both backends --------------------------
    # the online predictor (warm-started from the profiled table) rides
    # along: each decode step emits speculative prewarm hints and scores
    # them against the routing that actually happened
    predictor = rt.online_predictor(decay=0.98)
    eng = ServingEngine(rt.model, rt.params, max_len=128, batch_size=4,
                        predictor=predictor)
    serving = rt.serving_backend(eng)
    live = serving.execute(plan, workload)
    print(f"serving backend: billed ${live.billed_cost:.6f} for "
          f"{live.num_tokens} served tokens in "
          f"{len(live.extras['dispatch_rounds'])} dispatch rounds "
          f"(chunk={live.extras['chunk_tokens']}); "
          f"mean TTFT {1e3 * live.extras['mean_ttft_s']:.1f}ms; "
          f"reasons {live.extras['finish_reasons']}")
    spec = eng.speculation_stats()
    print(f"speculative dispatch: {spec['hits']}/{spec['pairs']} routed "
          f"pairs pre-warmed (hit rate {spec['hit_rate']:.0%}, "
          f"{spec['misses']} wasted hints)")

    sim = rt.simulator_backend()
    offline = sim.execute(plan, Workload(
        batches=[np.concatenate([p, np.asarray(r.output)]).astype(np.int32)
                 [None] for p, r in zip(prompts, serving.last_requests)]))
    print(f"simulator backend (same plan object): billed "
          f"${offline.billed_cost:.6f} "
          f"({offline.throughput_tps:.1f} tok/s)")

    # --- close the loop: re-plan from live telemetry + emit the diff -----
    tel = eng.telemetry
    assert tel is not None
    print(f"telemetry: {tel.prefill_tokens} prefill + {tel.decode_tokens} "
          f"decoded tokens across {rt.num_layers} MoE layers")
    live_plan = rt.plan_from_telemetry(tel)
    diff = live_plan.metadata["replan_diff"]
    print(f"re-planned from live traffic: methods {live_plan.method}; "
          f"replicas (layer 0): {live_plan.replicas[0]}")
    print(f"plan diff: {diff['replicas_changed']} replica cells changed "
          f"(+{diff['replicas_added']}/-{diff['replicas_removed']}), "
          f"{len(diff['method_changes'])} method changes, "
          f"cost delta ${diff['cost_delta']:+.6f}")

    # --- what-if: replay the session on a misbehaving platform -----------
    storm = FaultProfile(cold_start_prob=0.7, warm_pool=2, failure_prob=0.1)
    replay = rt.replay_telemetry_trace(tel, num_windows=4, faults=storm)
    cost = sum(r.billed_cost for r in replay["reports"])
    cold = sum(r.cold_starts for r in replay["reports"])
    retries = sum(r.retries for r in replay["reports"])
    print(f"replayed under a cold-start storm: billed ${cost:.6f} "
          f"({cold} cold starts, {retries} retries, "
          f"{replay['replans']} feedback re-plans)")

    # --- same storm, but the online predictor pre-warms each window ------
    from repro.traces import replay_telemetry
    warm = rt.run_trace(replay_telemetry(tel, num_windows=4),
                        plan=rt.last_plan, faults=storm, replan=False,
                        predictor=predictor, prewarm="predicted")
    w_cost = sum(r.billed_cost for r in warm["reports"])
    w_cold = sum(r.cold_starts for r in warm["reports"])
    hits = sum(r.prewarm_hits for r in warm["reports"])
    wasted = sum(r.wasted_prewarm_gb_s for r in warm["reports"])
    print(f"same storm with predictive pre-warming: billed ${w_cost:.6f} "
          f"({w_cold} cold starts, {hits} prewarm hits, "
          f"{wasted:.3f} wasted GB-s)")


if __name__ == "__main__":
    main()
