"""The decode-step program's share of its roofline, in %.

For every decode step the harness saw in the traced part of the window,
``counts.decode_step`` gives the operations and bytes that step needs
(live keys and values at each request's own length, the experts the
step touched, all other weights once); the least time is the larger of
operations over peak FLOP/s and bytes over peak bandwidth. The share is
the mean least time per step over the measured device time per decode
program."""
from chipbench import counts


def read(rec):
    tr, peak = rec.get("trace"), rec.get("peak")
    p = tr and tr["programs"].get("decode")
    steps = [s for s in rec.get("trace_steps", []) if s["decode"]]
    if not p or not p["count"] or not peak or not steps:
        return None
    least = sum(counts.least_seconds(
        counts.decode_step(rec["model"], s["decode"], s.get("touched")),
        peak)["seconds"] for s in steps) / len(steps)
    return 100.0 * least / (p["device_s"] / p["count"])
