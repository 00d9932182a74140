"""Run one benchmark cell once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``).
The run:

1. checks that JAX's devices are TPUs, as many as the cell asks for, and
   exits non-zero with no result line otherwise;
2. makes the weights on the device from ``--seed`` (``weights.py``) and
   builds the serving engine the way a deployment does: the program's
   defaults, with only the slots, ``max_len``, engine arguments and model
   options (``program``) the configuration file states;
3. warms up every program shape the window can reach (``warm_up``), from
   JAX's persistent compilation cache at a fixed path inside the checkout
   (or ``JAX_COMPILATION_CACHE_DIR``);
4. drives the window for ``--seconds`` on the host clock, keeping at
   least a batch of requests queued; with ``--trace 1`` the profiler
   records a few seconds in the middle of it;
5. runs the admitted requests to their end, checks a sample of what was
   served against the float32 reference (``reference/<config>.py``) and
   prints the result line.

End-to-end metrics (``--trace 0``) and per-layer metrics (``--trace 1``)
are read by ``metrics/<name>.py``, each from the run record; the cell's
metrics are the ones ``BENCHMARK.json`` lists for it.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from functools import partial  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from chipbench import traffic  # noqa: E402

TRACE_S = 3.0           # length of the traced part of a --trace 1 window
TRACE_AT = 0.4          # where it starts, as a share of the window
SPAN = "chipbench."     # prefix of the harness's host spans


class Refused(Exception):
    """The run cannot be made here; no result line is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ inputs
def load_json(path: Path) -> Dict:
    if not path.is_file():
        raise Refused(f"missing {path}")
    return json.loads(path.read_text())


def load_cell(name: str):
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(ROOT / cfg_entry["file"])
    mix = traffic.load(cell["traffic"])
    return bench, cell, cfg, mix


def cell_metrics(bench: Dict, cell: str, kind: str) -> List[Dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise Refused(f"missing {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(defs: List[Dict], rec: Dict) -> Dict:
    out = {}
    for m in defs:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "chipbench_metric_" + m["name"].replace(".", "_"))
        v = reader.read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ device
def peaks_for(kind: str) -> Dict:
    """The chip's published peaks; a device the table lacks is an error."""
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise Refused(f"no peaks for {kind!r} in peaks.json")
    return peaks[kind]


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devs[0]}")
    if len(devs) < n:
        raise Refused(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def use_compile_cache() -> str:
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class CompileCounter:
    """Counts executables built or loaded while ``on`` (a new shape)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.count += 1


# ----------------------------------------------------------------- program
MODEL_KEYS = {  # configuration key -> how the program's config states it
    "num_layers": lambda c: c.num_layers,
    "d_model": lambda c: c.d_model,
    "num_heads": lambda c: c.num_heads,
    "num_kv_heads": lambda c: c.num_kv_heads,
    "head_dim": lambda c: c.resolved_head_dim,
    "d_expert_ff": lambda c: c.moe.d_expert_ff,
    "num_experts": lambda c: c.moe.num_experts,
    "top_k": lambda c: c.moe.top_k,
    "vocab_size": lambda c: c.vocab_size,
    "max_seq_len": lambda c: c.max_seq_len,
    "norm": lambda c: c.norm,
    "pos": lambda c: c.pos_embed,
    "act": lambda c: c.activation,
    "tied": lambda c: c.tie_embeddings,
    "dtype": lambda c: c.dtype,
}


def program_field(mc, path: str):
    """The field at the dotted ``path`` of the program's model config
    (``moe.num_shared_experts``), as JSON would state it: a dataclass as
    the list of its fields, a tuple as a list."""
    v = mc
    for name in path.split("."):
        v = getattr(v, name)

    def plain(x):
        if dataclasses.is_dataclass(x):
            x = dataclasses.astuple(x)
        return [plain(y) for y in x] if isinstance(x, (tuple, list)) else x

    return plain(v)


def build(cfg: Dict, seed: int, model_cfg=None):
    """The program under test, with benchmark-made weights. The
    configuration file's ``program`` options (fields of the program's
    model config, such as tying) are set as it states them. Every
    ``MODEL_KEYS`` key of the file's ``model``, and every key its
    ``program_keys`` names (``model`` key -> dotted field of the program's
    config), must equal the program's."""
    import jax
    import jax.numpy as jnp

    import repro.configs  # noqa: F401  (registers the architectures)
    from repro.config import get_arch
    from repro.models import Model
    from repro.serving import ServingEngine

    from chipbench import weights

    mc = dataclasses.replace(model_cfg or get_arch(cfg["arch"]),
                             **cfg.get("program", {}))
    m = cfg["model"]
    keys = dict(MODEL_KEYS)
    for k, path in cfg.get("program_keys", {}).items():
        keys[k] = partial(program_field, path=path)
    wrong = {k: (m[k], f(mc)) for k, f in keys.items() if m[k] != f(mc)}
    if m["pos"] == "rope" and mc.rope_theta != m["rope_theta"]:
        wrong["rope_theta"] = (m["rope_theta"], mc.rope_theta)
    if wrong:
        raise Refused(f"the program's {cfg['arch']} differs from the "
                      f"configuration file: {wrong}")
    model = Model(mc)
    dtype = jnp.dtype(m["dtype"])
    shapes = jax.eval_shape(lambda k: model.init_params(k, dtype=dtype),
                            jax.random.PRNGKey(0))
    params = weights.make(shapes, seed)
    s = cfg["serving"]
    eng = ServingEngine(model, params, max_len=s["max_len"],
                        batch_size=s["slots"], **s.get("engine", {}))
    return params, eng


def warm_up(eng, mix: Dict, vocab: int) -> int:
    """Load (or compile) every program shape the window can reach, and no
    other. Through the engine's own submit/step: one request of every grid
    prompt length, prefilled and ended at admission, and one request
    decoded for a few steps (the host side of a step). Then the engine's
    decode-step program once at every ``kv_len`` bucket from the shortest
    prompt's to the longest live request's (the bucket follows the
    longest live request), on the engine's cache, whose rows no live
    request holds. Returns the decode steps run."""
    import jax.numpy as jnp

    grid = traffic.prompt_grid(mix["prompt"])
    rng = np.random.default_rng(0)
    for n in grid:
        eng.submit(rng.integers(0, vocab, int(n)).astype(np.int32),
                   max_new_tokens=1)
    eng.submit(rng.integers(0, vocab, int(grid[0])).astype(np.int32),
               max_new_tokens=4)
    steps = 0
    while eng.step():
        steps += 1
    if eng.pending or eng.kv.max_valid_len():
        raise RuntimeError("warm-up left requests queued or live")
    kv_lens = [None]
    if eng._ragged_decode:
        b = eng.kv_len_bucket
        kv_lens = sorted({min(-(-(n + 1) // b) * b, eng.max_len) for n in
                          range(int(grid[0]), traffic.longest_live(mix))})
    toks = jnp.asarray(np.zeros((eng.num_slots, 1), np.int32))
    pos = jnp.asarray(np.zeros(eng.num_slots, np.int32))
    for kv in kv_lens:
        _, cache, _ = eng._jit_decode(eng.params, toks, eng.kv.cache, pos,
                                      None, kv)
        eng.kv.update(cache)
        steps += 1
    if eng.telemetry is not None:
        eng.telemetry.reset()
    return steps


# ------------------------------------------------------------------ window
def span(name: str):
    """A host span in the profiler's trace (costs next to nothing when the
    profiler is off)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN + name)


def serve(eng, mix: Dict, vocab: int, seed: int, seconds: float,
          counter: CompileCounter, trace_dir: Optional[str]) -> Dict:
    """Drive the window: the queue holds at least a batch of requests
    throughout, each due when it is queued. When ``seconds`` are up no
    new step starts; the window closes as the step under way ends, so
    that every token of every step it ran counts, over all of its time.
    Returns the run record the metrics read."""
    import jax

    slots = eng.num_slots
    src = traffic.stream(mix, vocab, seed)
    recs: List[Dict] = []
    live: List[Dict] = []
    steps: List[Dict] = []          # per step inside the traced part
    tel = eng.telemetry
    demand0 = tel.demand.copy() if tel is not None else None
    t_tr0 = seconds * TRACE_AT
    tracing, traced, win_ann = False, False, None
    prev_demand = None
    counter.on = True
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t = 0.0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        with span("generator"):
            while eng.pending < slots:
                r = next(src)
                req = eng.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                rec = {"req": req, "due": now - t0, "prompt": len(r.prompt),
                       "max_new": r.max_new_tokens, "times": []}
                recs.append(rec)
                live.append(rec)
        with span("engine_step"):
            eng.step()
        t = time.perf_counter() - t0
        with span("bookkeeping"):
            step = {"prefill": [], "decode": []} if tracing else None
            keep = []
            for rec in live:
                req = rec["req"]
                before, after = len(rec["times"]), len(req.output)
                if after > before:
                    rec["times"].extend([t] * (after - before))
                    if step is not None:
                        if before == 0:
                            step["prefill"].append(rec["prompt"])
                        step["decode"].extend(
                            rec["prompt"] + n
                            for n in range(max(before, 1), after))
                if req.done:
                    rec["finish"] = t
                    rec["reason"] = req.finish_reason
                else:
                    keep.append(rec)
            live = keep
            if step is not None:
                if tel is not None:
                    d = tel.demand.copy()
                    step["touched"] = (((d - prev_demand) > 0).sum(1).tolist()
                                       if prev_demand is not None else None)
                    prev_demand = d
                steps.append(step)
        if trace_dir is not None and not traced:
            if not tracing and t >= t_tr0:
                jax.profiler.start_trace(trace_dir)
                win_ann = span("window")
                win_ann.__enter__()
                tracing, tr_start = True, time.perf_counter() - t0
                prev_demand = tel.demand.copy() if tel is not None else None
            elif tracing and t >= tr_start + TRACE_S:
                win_ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing, traced = False, True
    counter.on = False
    if tracing:
        win_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {
        "window_s": t,
        "requests": recs,
        "tokens_in_window": sum(len(r["times"]) for r in recs),
        "compiles_in_window": counter.count,
        "trace_steps": steps[1:],
        "demand": (tel.demand - demand0) if tel is not None else None,
    }


def drain(eng, rec: Dict) -> int:
    """After the window: drop the requests still queued (never admitted,
    so never served) and run the admitted ones to their end through the
    same engine, so that the check can see the longest of them. Returns
    the decode steps this took."""
    eng.scheduler.queue.clear()
    n = 0
    while eng.step():
        n += 1
    for r in rec["requests"]:
        req = r["req"]
        if req.done and r.get("reason") is None:
            r["reason"] = req.finish_reason
    return n


# ------------------------------------------------------------- correctness
def sample_finished(recs: List[Dict], seed: int, chk: Dict) -> List[Dict]:
    """Finished requests drawn from the seed, the longest among them,
    until there are ``sample_requests`` of them holding ``sample_tokens``
    served tokens, ``long_tokens`` of those served past ``long_from``
    tokens of context (or every finished request)."""
    done = [r for r in recs if r.get("reason") is not None]
    if not done:
        return []

    def long_n(r):
        return max(0, r["prompt"] + len(r["req"].output) - chk["long_from"])

    longest = max(range(len(done)),
                  key=lambda i: done[i]["prompt"] + len(done[i]["req"].output))
    pick = [done[longest]]
    n, n_long = len(done[longest]["req"].output), long_n(done[longest])
    for i in np.random.default_rng(seed + 1).permutation(len(done)):
        if (len(pick) >= chk["sample_requests"] and n >= chk["sample_tokens"]
                and n_long >= chk["long_tokens"]):
            break
        if i != longest:
            pick.append(done[i])
            n += len(done[i]["req"].output)
            n_long += long_n(done[i])
    return pick


def check(cfg: Dict, params, sample: List[Dict], control: bool,
          near_tie: Optional[float] = None) -> Dict:
    """Every sampled request's served tokens against the reference: the
    gap of each served token (``reference/common.py``), and with
    ``control`` the gap of the token the float8 control puts first. Per
    request: its gaps, and each token's context (the tokens before it).
    The configuration's reference module gives ``arch(cfg)`` and, where
    its layers are not the default ones, ``layers(cfg, params)``."""
    ref = load_module(BENCH / "reference" / f"{cfg['name']}.py",
                      "chipbench_reference")
    from chipbench.reference import common

    arch = ref.arch(cfg)
    layers = ref.layers(cfg, params) if hasattr(ref, "layers") else None
    chk = cfg["check"]
    nt = chk["near_tie"] if near_tie is None else near_tie
    reqs, paths, bad = [], 0, 0
    for rec in sample:
        req = rec["req"]
        served = np.asarray(req.output, np.int64)
        if rec["reason"] != "length" or len(served) != rec["max_new"]:
            bad += 1
            continue
        toks = np.concatenate([np.asarray(req.prompt, np.int64),
                               served[:-1]])
        first = len(req.prompt) - 1
        rows, row_pos = common.admissible_rows(
            arch, params, toks, first, near_tie=nt,
            max_flips=chk["max_flips"], pad_to=cfg["serving"]["max_len"],
            layers=layers)
        paths += len(row_pos) - len(served)
        one = {"gaps": common.served_gaps(rows, row_pos, served),
               "context": len(req.prompt) + np.arange(len(served)),
               "slot": req.slot}
        if control:
            ctl = common.control_tokens(arch, params, toks, first,
                                        pad_to=cfg["serving"]["max_len"],
                                        layers=layers)
            one["control_gaps"] = common.served_gaps(rows, row_pos, ctl)
        reqs.append(one)
        del rows
    return {"requests": reqs, "paths": paths, "bad_finish": bad}


def gap_stats(res: Dict, chk: Dict, key: str = "gaps") -> Dict:
    """The numbers a configuration may compare (``check.limits``), over
    the compared requests' ``key`` gaps: the 95th percentile of all, and
    of those served past ``long_from`` tokens of context, and the mean of
    all. With nothing to read, a number is infinite (and fails). The
    widest gap is printed, not compared."""
    reqs = res["requests"]

    def p95(g):
        return float(np.quantile(g, 0.95)) if len(g) else float("inf")

    allg = (np.concatenate([r[key] for r in reqs]) if reqs
            else np.zeros(0))
    long_g = (np.concatenate([r[key][r["context"] >= chk["long_from"]]
                              for r in reqs]) if reqs else np.zeros(0))
    return {"gap_p95": p95(allg), "long_gap_p95": p95(long_g),
            "tokens": int(len(allg)), "long_tokens": int(len(long_g)),
            "max": float(allg.max()) if len(allg) else float("inf"),
            "gap_mean": float(allg.mean()) if len(allg) else float("inf")}


# -------------------------------------------------------------------- main
def run(cell: Dict, cfg: Dict, mix: Dict, bench: Dict, seed: int,
        seconds: float, trace: bool, control: bool = False,
        chips: Optional[list] = None, model_cfg=None) -> Dict:
    """One run of a cell; returns the result object (the last line)."""
    import jax

    from chipbench import trace_reduce

    devs = chips if chips is not None else jax.devices()[:cell["chips"]]
    counter = CompileCounter()
    params, eng = build(cfg, seed, model_cfg)
    vocab = cfg["model"]["vocab_size"]
    w_steps = warm_up(eng, mix, vocab)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    setup_s = time.perf_counter() - PROCESS_START
    log(f"setup: {setup_s:.3f} s (warm-up {w_steps} decode steps)")
    try:
        rec = serve(eng, mix, vocab, seed, seconds, counter, trace_dir)
        rec["setup_s"] = setup_s
        red = None
        if trace:
            red = trace_reduce.reduce(
                trace_reduce.events(trace_dir),
                {"decode": "_decode_impl", "prefill": "_prefill_impl"})
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    chk = cfg["check"]
    log(f"window: {len(rec['requests'])} requests due, "
        f"{rec['tokens_in_window']} tokens in {rec['window_s']:.3f} s; "
        f"compilations "
        f"inside the window: {rec['compiles_in_window']}")
    t_dr = time.perf_counter()
    d_steps = drain(eng, rec)
    log(f"drain: the admitted requests run to their end in {d_steps} "
        f"decode steps, {time.perf_counter() - t_dr:.1f} s")
    stats = [d.memory_stats() or {} for d in devs]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    log(f"peak_bytes_in_use {peak} (fullest chip)")

    kind = devs[0].device_kind
    rec.update(model=cfg["model"], trace=red,
               peak=peaks_for(kind) if devs[0].platform == "tpu" else None)
    defs = cell_metrics(bench, cell["name"],
                        "per_layer" if trace else "end_to_end")
    metrics = read_metrics(defs, rec)

    # correctness: after the window, with the program's state freed
    sample = sample_finished(rec["requests"], seed, chk)
    del eng
    for r in rec["requests"]:
        if r not in sample:
            r.pop("req", None)
    gc.collect()
    t_ref = time.perf_counter()
    res = check(cfg, params, sample, control)
    st = gap_stats(res, chk)
    log(f"reference: {len(sample)} requests, {st['tokens']} served "
        f"tokens, {res['paths']} routing paths, in "
        f"{time.perf_counter() - t_ref:.1f} s; widest gap {st['max']!r} "
        f"(not compared)")
    def shown(v):  # JSON has no infinity; nothing to read prints null
        return v if np.isfinite(v) else None

    checks = {name: {"value": shown(st[name]), "limit": lim}
              for name, lim in chk["limits"].items()}
    if control:
        ctl = gap_stats(res, chk, "control_gaps")
        for name, lim in chk["limits"].items():
            checks["control_" + name] = {"value": shown(ctl[name]),
                                         "limit": lim}
    least = {"requests_compared": (len(sample), chk["sample_requests"]),
             "served_tokens_compared": (st["tokens"], chk["sample_tokens"]),
             "long_tokens_compared": (st["long_tokens"], chk["long_tokens"])}
    for name, (v, lim) in least.items():
        checks[name] = {"value": v, "least": lim}
    checks["bad_finish"] = {"value": res["bad_finish"], "limit": 0}
    correct = (all(st[name] <= lim for name, lim in chk["limits"].items())
               and all(v >= lim for v, lim in least.values())
               and res["bad_finish"] == 0)
    failed = sum(1 for r in rec["requests"]
                 if r.get("reason") not in (None, "length"))
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": peak}
    # requests still queued at the close never reached the server
    attempted = sum(1 for r in rec["requests"]
                    if r["times"] or r.get("reason"))
    out = {"correct": correct, "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = checks
    for name, c in checks.items():
        bound = (f"limit {c['limit']}" if "limit" in c
                 else f"at least {c['least']}")
        log(f"check {name}: {c['value']!r} ({bound})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        bench, cell, cfg, mix = load_cell(a.workload)
        devs = require_chips(cell["chips"])
        peaks_for(devs[0].device_kind)
        log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
            f"compile cache {use_compile_cache()}")
        out = run(cell, cfg, mix, bench, a.seed, a.seconds, bool(a.trace),
                  chips=devs)
    except (Refused, ImportError) as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
