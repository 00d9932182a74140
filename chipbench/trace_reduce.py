"""From a profiler trace to device busy time, idle gaps and program time.

Two steps, kept apart so the second can be tested on a small recorded
trace without a chip:

1. :func:`events` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
   into plain lists: per device plane (``/device:TPU:n``) the events of its
   ``XLA Modules`` and ``XLA Ops`` lines, and the host's trace annotations
   (names starting with ``SPAN_PREFIX``) on every host line.
2. :func:`reduce` clips them to the traced window (the host span
   ``<SPAN_PREFIX>window``) and returns busy and window seconds (busy
   averaged over device planes), device seconds and call counts per
   jitted program, the device ops that took most time, and the longest
   idle gaps, each named by the host span that covers most of it.

A jitted program is found by its function's name inside the module name
(``jit__decode_impl(…)`` holds ``_decode_impl``). Times are in seconds.
"""
from __future__ import annotations

import glob
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
MODULES, OPS = "XLA Modules", "XLA Ops"


def events(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


def _clip(evs, t0, t1) -> List[Tuple[float, float, str]]:
    out = []
    for name, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b, name))
    return out


def _union(iv: Sequence[Tuple[float, float, str]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b, _ in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _op_name(name: str) -> str:
    return name.split(" = ")[0].lstrip("%").strip()


def reduce(ev: Dict, programs: Dict[str, str], top: int = 10) -> Optional[Dict]:
    """Reduce :func:`events` output; None when no device ran an
    operation inside the window."""
    win = [e for e in ev["host"] if e[0] == SPAN_PREFIX + "window"]
    if not win or not ev["devices"]:
        return None
    t0, t1 = win[0][1], win[0][1] + win[0][2]
    spans = [e for e in _clip(ev["host"], t0, t1)
             if e[2] != SPAN_PREFIX + "window"]
    busy, prog, ops, gaps = [], {}, {}, []
    for lines in ev["devices"].values():
        mods = _clip(lines.get(MODULES, []), t0, t1)
        merged = _union(mods)
        busy.append(sum(b - a for a, b in merged))
        for key, fn in programs.items():
            hit = [b - a for a, b, n in mods if fn in n]
            p = prog.setdefault(key, {"count": 0, "device_s": 0.0})
            p["count"] += len(hit)
            p["device_s"] += sum(hit)
        for a, b, n in _clip(lines.get(OPS, []), t0, t1):
            k = _op_name(n)
            ops[k] = ops.get(k, 0.0) + (b - a)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n_dev = len(ev["devices"])
    for p in prog.values():
        p["count"] = p["count"] / n_dev
        p["device_s"] = p["device_s"] / n_dev
    named = []
    for length, a, b in sorted(gaps, reverse=True)[:top]:
        cover: Dict[str, float] = {}
        for s, e, n in spans:
            o = min(b, e) - max(a, s)
            if o > 0:
                cover[n] = cover.get(n, 0.0) + o
        name = max(cover, key=cover.get)[len(SPAN_PREFIX):] if cover \
            else "no host span"
        named.append([name, length])
    if not sum(busy):
        return None
    return {
        "window_s": t1 - t0,
        "busy_s": sum(busy) / n_dev,
        "programs": prog,
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": named,
    }
