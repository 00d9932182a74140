"""Readings that the correctness limits are set from, many seeds in one
process (the benchmark's own runs never run this).

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--near-ties 0,0.1] [--control 1]

For each seed: fresh weights from the seed in the same engine, a window
of the cell's own traffic and load, the admitted requests drained, the
finished requests sampled as a run samples them, and the numbers a run
compares, for the served tokens (and, with ``--control 1``, for the
float8 control's tokens) in each near-tie band, with each compared
request's slot, tokens, last context, and its own 95th percentile and
mean (the program's, then the control's). One JSON
line per seed and band.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--near-ties", default="")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args(argv)
    import jax

    from chipbench import weights

    bench, cell, cfg, mix = R.load_cell(a.workload)
    R.require_chips(cell["chips"])
    R.use_compile_cache()
    seeds = [int(s) for s in a.seeds.split(",")]
    bands = ([float(x) for x in a.near_ties.split(",")] if a.near_ties
             else [cfg["check"]["near_tie"]])
    counter = R.CompileCounter()
    params, eng = R.build(cfg, seeds[0])
    vocab = cfg["model"]["vocab_size"]
    R.warm_up(eng, mix, vocab)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          params)
    out = open(a.out, "a") if a.out else None
    chk = cfg["check"]
    for i, seed in enumerate(seeds):
        if i:
            eng.params = params = None
            eng.params = params = weights.make(shapes, seed)
        counter.count = 0
        rec = R.serve(eng, mix, vocab, seed, a.seconds, counter, None)
        R.drain(eng, rec)
        if eng.telemetry is not None:
            eng.telemetry.reset()
        sample = R.sample_finished(rec["requests"], seed, chk)
        for nt in bands:
            t = time.perf_counter()
            res = R.check(cfg, params, sample, bool(a.control), near_tie=nt)
            line = {"workload": a.workload, "seed": seed, "near_tie": nt,
                    "requests": len(sample), "paths": res["paths"],
                    "bad_finish": res["bad_finish"],
                    "compiles_in_window": rec["compiles_in_window"],
                    "tokens_in_window": rec["tokens_in_window"],
                    "program": R.gap_stats(res, chk),
                    "control": (R.gap_stats(res, chk, "control_gaps")
                                if a.control else None),
                    "per_request": [
                        [int(r["slot"]), len(r["gaps"]),
                         int(r["context"][-1]),
                         float(np.quantile(r["gaps"], 0.95)),
                         float(np.mean(r["gaps"])),
                         (float(np.quantile(r["control_gaps"], 0.95))
                          if a.control else None),
                         (float(np.mean(r["control_gaps"]))
                          if a.control else None)]
                        for r in res["requests"]],
                    "reference_s": time.perf_counter() - t}
            print(json.dumps(line), flush=True)
            if out:
                out.write(json.dumps(line) + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
