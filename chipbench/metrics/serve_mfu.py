"""Model FLOP/s utilization of the whole served path, in %: the
operations of every token served in the traced part of the window
(each prefill at its prompt length, each decoded token against its
context; twice the active parameters plus attention) over the traced
window times the chip's peak FLOP/s."""
from chipbench import counts


def read(rec):
    tr, peak = rec.get("trace"), rec.get("peak")
    steps = rec.get("trace_steps", [])
    if not tr or not peak or not steps:
        return None
    m = rec["model"]
    flops = sum(counts.prefill(m, n)["flops"] for s in steps
                for n in s["prefill"])
    flops += sum(counts.token_flops(m, n) for s in steps for n in s["decode"])
    return 100.0 * flops / (tr["window_s"] * peak["flops_per_s"])
