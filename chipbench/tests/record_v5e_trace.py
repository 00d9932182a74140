"""Record the small chip trace that ``test_chipbench_span_reduce.py``
reads (``data/trace_spans_v5e.json``): a tiny gpt2-moe engine
(``chipbench_tiny``) serves a few requests on one TPU under the
harness's spans and the program's own; the trace is kept as
:func:`chipbench.span_reduce.events` reads it (only the lines and stats
the reduction uses), cut to one engine step that admits a request and
decodes (:func:`cut`), with op names cut at `` = ``.

    python3 chipbench/tests/record_v5e_trace.py <out.json> [<raw dir>]

With a raw directory the profiler's ``.xplane.pb`` is copied there too.
"""
import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (str(HERE.parents[1] / "src"), str(HERE.parents[1]), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import run as R  # noqa: E402
from chipbench import span_reduce  # noqa: E402
from chipbench_tiny import config  # noqa: E402

PROMPTS, NEW = (9, 14, 6, 11, 20, 7), (5, 3, 6, 2, 4, 3)


def serve(eng, vocab, traced):
    rng = np.random.default_rng(7)
    for n, k in zip(PROMPTS, NEW):
        eng.submit(rng.integers(0, vocab, n).astype(np.int32),
                   max_new_tokens=k)
    if not traced:
        while eng.step():
            pass
        return
    with R.span("window"):
        while True:
            with R.span("engine_step"):
                live = eng.step()
            if not live:
                break


def cut(ev):
    """Keep the first engine step after the first that starts both a
    prefill and a decode program, as the window, and the events that
    overlap it."""
    def starts(fn, a, b):
        return any(fn in m[0] and a <= m[1] < b for dev in
                   ev["devices"].values() for m in dev["XLA Modules"])

    steps = [h for h in ev["host"] if h[0] == R.SPAN + "engine_step"][1:]
    _, a, d = next(h for h in steps if starts("_prefill_impl", h[1],
                                              h[1] + h[2])
                   and starts("_decode_impl", h[1], h[1] + h[2]))
    b = a + d

    def near(evs):
        return [e for e in evs if e[1] < b and e[1] + e[2] > a]

    return {
        "devices": {name: {
            "XLA Modules": near(lines["XLA Modules"]),
            "XLA Ops": [[n.split(" = ")[0], *rest]
                        for n, *rest in near(lines["XLA Ops"])]}
            for name, lines in ev["devices"].items()},
        "host": [[R.SPAN + "window", a, d]] + [
            h for h in near(ev["host"]) if h[0] != R.SPAN + "window"]}


def write(out: str, ev) -> None:
    ev = {"about": "Recorded on one TPU v5 lite by record_v5e_trace.py: a "
                   "tiny gpt2-moe engine serving under the harness's "
                   "engine_step spans and the program's serving. spans "
                   "and model scopes, as span_reduce.events reads it, cut "
                   "to one engine step that admits a request and decodes "
                   "(the window).", **ev}
    Path(out).write_text(json.dumps(ev, indent=0) + "\n")


def main(out: str, raw: str = None) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    cfg, mc = config("gpt2-moe", slots=4)
    _, eng = R.build(cfg, 5, mc)
    vocab = cfg["model"]["vocab_size"]
    serve(eng, vocab, False)            # compile every shape first
    d = tempfile.mkdtemp(prefix="record-trace-")
    try:
        jax.profiler.start_trace(d)
        serve(eng, vocab, True)
        jax.profiler.stop_trace()
        ev = span_reduce.events(d)
        if raw:
            Path(raw).mkdir(parents=True, exist_ok=True)
            for f in glob.glob(f"{d}/**/*.xplane.pb", recursive=True):
                shutil.copy(f, raw)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    write(out, cut(ev))


if __name__ == "__main__":
    main(*sys.argv[1:])
