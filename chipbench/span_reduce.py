"""The program's own spans and scopes in a profiler trace: device time by
model scope, and device-idle time by the serving engine's host span.

    python3 chipbench/span_reduce.py --workload <cell> --seed <n> \
        [--seconds <s>]

runs a cell's window as ``run.py`` does, with the profiler on for its
middle ``run.TRACE_S`` seconds, and prints one JSON line: the readings of
:func:`readings`, and the ``scopes``, ``idle_by_span`` and ``idle_gaps``
tables of :func:`reduce`. No reference check is made; ``run.py`` does
that.

The reduction extends :mod:`trace_reduce`, whose answers it keeps:

1. :func:`events` reads an ``.xplane.pb`` as :func:`trace_reduce.events`
   does, and also keeps the program's host spans (names starting with
   ``PROGRAM_PREFIX``, written by ``repro.serving.engine``) and, as a
   fourth item of each op event, the ``op_name`` path JAX gave the
   operation. A TPU trace holds it in the ``tf_op`` stat of the op's
   event metadata
   (``jit(_decode_impl)/while/body/closed_call/moe/router/dot_general:``),
   which ``ProfileData`` does not expose, so :func:`op_names` reads it
   from the ``.xplane.pb`` itself; the model's ``jax.named_scope`` names
   are in it.
2. :func:`reduce` returns :func:`trace_reduce.reduce`'s answer with
   ``scopes``, per jitted program the device seconds of its leaf ops by
   scope (:func:`scope_of`), and ``idle_by_span``, the device's idle time
   split over the innermost program span above it (``NO_SPAN`` where
   none is); an idle gap that a program span covers in part is named
   ``<harness span>/<innermost program span covering most of it>``
   (``engine_step/serving.telemetry``).

Times are in seconds.
"""
from __future__ import annotations

import bisect
import glob
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import trace_reduce  # noqa: E402
from chipbench.trace_reduce import (MODULES, OPS, SPAN_PREFIX,  # noqa: E402
                                    _clip, _union)

PROGRAM_PREFIX = "serving."
PROGRAMS = {"decode": "_decode_impl", "prefill": "_prefill_impl"}
UNSCOPED, NO_SPAN = "(unscoped)", "(none)"
# op_name frames JAX adds for its own control flow, not the program's
JAX_FRAMES = {"while", "body", "cond", "closed_call", "checkpoint",
              "remat", "rematted_computation", "branch", "switch"}
# program spans whose idle time each reading gathers
ADMIT = ("serving.admit", "serving.prefill", "serving.kv_insert")


def _xspace_class():
    """A message class for the parts of the profiler's ``XSpace`` proto
    (``tsl/profiler/protobuf/xplane.proto``) that hold event metadata:
    each plane's name, its event metadata (name and stats) and its stat
    names. Field numbers as in that file; the rest is skipped."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")

    def message(parent, name, fields):
        m = parent.add(name=name)
        for fname, num, typ, ref in fields:
            f = m.field.add(name=fname, number=num, type=typ,
                            label=F.LABEL_REPEATED if ref and ref[0] == "*"
                            else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".chipbench." + ref.lstrip("*")
        return m

    I, S, M = F.TYPE_INT64, F.TYPE_STRING, F.TYPE_MESSAGE
    message(fd.message_type, "XStat", [("metadata_id", 1, I, None),
                                       ("str_value", 5, S, None)])
    message(fd.message_type, "XEventMetadata", [
        ("name", 2, S, None), ("stats", 5, M, "*XStat")])
    message(fd.message_type, "XStatMetadata", [("name", 2, S, None)])
    plane = message(fd.message_type, "XPlane", [
        ("name", 2, S, None),
        ("event_metadata", 4, M, "*XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, M, "*XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                         ("StatMetadataEntry", "XStatMetadata")):
        e = message(plane.nested_type, entry,
                    [("key", 1, I, None), ("value", 2, M, value)])
        e.options.map_entry = True
    message(fd.message_type, "XSpace", [("planes", 1, M, "*XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Per device plane, each op's event name to its ``op_name`` (the
    ``tf_op`` stat of its event metadata, less the ``:<type>`` suffix)."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _xspace_class().FromString(xspace).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        tf_op = {k for k, v in plane.stat_metadata.items()
                 if v.name == "tf_op"}
        out[plane.name] = {
            md.name: st.str_value.rsplit(":", 1)[0]
            for md in plane.event_metadata.values()
            for st in md.stats if st.metadata_id in tf_op}
    return out


def events(trace_dir: str) -> Dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(files[-1], "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    scopes = op_names(raw)
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            names = scopes.get(plane.name, {})
            lines = {}
            for line in plane.lines:
                if line.name == MODULES:
                    lines[MODULES] = [
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                        for e in line.events]
                elif line.name == OPS:
                    lines[OPS] = [
                        [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         names.get(e.name, "")]
                        for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events
                    if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)))
    return out


def scope_of(op_name: str) -> str:
    """The program's scope path of an op from JAX's ``op_name``: the
    named scopes between the program's own ``jit(...)`` frames and the
    primitive, less JAX's control-flow frames and einsum specs, and cut
    at a nested jitted function (a library's internals). Where XLA merged
    ops, the first op's name counts. No scope reads ``UNSCOPED``."""
    parts = op_name.split(";")[0].split("/")[:-1]
    while parts and parts[0].startswith(("jit(", "pjit(")):
        parts.pop(0)
    keep = []
    for c in parts:
        if "(" in c:
            break
        if c not in JAX_FRAMES and "->" not in c:
            keep.append(c)
    return "/".join(keep) or UNSCOPED


def _leaves(ops) -> List:
    """The op events that hold no other op event of the line: not the
    containers (``while``, ``conditional``, ``call``), whose ops the line
    lists too."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    tol = 1e-9
    out = []
    for i, e in enumerate(evs):
        end = e[1] + e[2]
        if i + 1 < len(evs):
            n = evs[i + 1]
            if n[1] < end and n[1] + n[2] <= end + tol and n[2] < e[2]:
                continue
        out.append(e)
    return out


def _innermost(spans, t0, t1) -> List[Tuple[float, float, str]]:
    """[t0, t1] cut into pieces, each named by the innermost program span
    over it (the one that started last) or ``NO_SPAN``."""
    bounds = sorted({t0, t1} | {x for s, e, _ in spans for x in (s, e)
                                if t0 < x < t1})
    order = sorted(spans)
    pieces: List[Tuple[float, float, str]] = []
    active: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(order) and order[j][0] <= a:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        name = max(active, key=lambda sp: (sp[0], -sp[1]))[2] if active \
            else NO_SPAN
        if pieces and pieces[-1][2] == name and pieces[-1][1] == a:
            pieces[-1] = (pieces[-1][0], b, name)
        else:
            pieces.append((a, b, name))
    return pieces


def _split(intervals, pieces) -> Dict[str, float]:
    """Seconds of the sorted ``intervals`` under each named piece."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in intervals:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            o = min(b, pieces[k][1]) - max(a, pieces[k][0])
            if o > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + o
            k += 1
    return out


def _harness_view(ev: Dict) -> Dict:
    """``ev`` as :func:`trace_reduce.events` gives it: op events without
    their ``op_name``, and only the harness's host spans."""
    return {
        "devices": {name: {k: [e[:3] for e in evs]
                           for k, evs in lines.items()}
                    for name, lines in ev["devices"].items()},
        "host": [e for e in ev["host"] if e[0].startswith(SPAN_PREFIX)]}


def reduce(ev: Dict, programs: Dict[str, str] = PROGRAMS,
           top: int = 10) -> Optional[Dict]:
    """:func:`trace_reduce.reduce` of ``ev``, with ``scopes``,
    ``idle_by_span`` and the idle gaps named down to the program span;
    None where that gives None."""
    red = trace_reduce.reduce(_harness_view(ev), programs, top)
    if red is None:
        return None
    win = next(e for e in ev["host"] if e[0] == SPAN_PREFIX + "window")
    t0, t1 = win[1], win[1] + win[2]
    pieces = _innermost([e for e in _clip(ev["host"], t0, t1)
                         if e[2].startswith(PROGRAM_PREFIX)], t0, t1)
    idle_by = {n: 0.0 for *_, n in pieces}
    gaps, scopes = [], {}
    for lines in ev["devices"].values():
        calls = sorted((s, s + d, n) for n, s, d in lines.get(MODULES, []))
        starts = [a for a, _, _ in calls]
        for op in _leaves(lines.get(OPS, [])):
            clipped = _clip([op[:3]], t0, t1)
            i = bisect.bisect_right(starts, op[1]) - 1
            if not clipped or i < 0 or op[1] >= calls[i][1]:
                continue
            key = next((k for k, fn in programs.items()
                        if fn in calls[i][2]), None)
            if key is not None:
                sc = scopes.setdefault(key, {})
                s = scope_of(op[3]) if len(op) > 3 else UNSCOPED
                sc[s] = sc.get(s, 0.0) + clipped[0][1] - clipped[0][0]
        merged = _union(_clip(lines.get(MODULES, []), t0, t1))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps.extend((b - a, a, b) for a, b in idle)
        for n, v in _split(idle, pieces).items():
            idle_by[n] += v
    # the gaps in trace_reduce's order, so its names line up with them
    named = []
    for (name, length), (_, a, b) in zip(
            red["idle_gaps"], sorted(gaps, reverse=True)[:top]):
        inner = _split([(a, b)], pieces)
        inner.pop(NO_SPAN, None)
        if inner:
            name += "/" + max(inner, key=inner.get)
        named.append([name, length])
    n_dev = len(ev["devices"])
    return dict(
        red, idle_gaps=named,
        scopes={k: {s: v / n_dev for s, v in sorted(
            sc.items(), key=lambda kv: -kv[1])} for k, sc in scopes.items()},
        idle_by_span={n: v / n_dev for n, v in sorted(
            idle_by.items(), key=lambda kv: -kv[1])})


def scope_s(red: Optional[Dict], program: str, scope: str) -> Optional[float]:
    """Device seconds of ``program``'s leaf ops under ``scope`` or a scope
    inside it, from :func:`reduce`'s ``scopes``; None where none is."""
    sc = (red or {}).get("scopes", {}).get(program, {})
    hit = [v for k, v in sc.items()
           if k == scope or k.startswith(scope + "/")]
    return sum(hit) if hit else None


def idle_share(red: Optional[Dict], names: Sequence[str]) -> Optional[float]:
    """Device-idle time under the program spans ``names`` (innermost), in
    % of the traced window; None where the window has none of them."""
    by = (red or {}).get("idle_by_span", {})
    hit = [by[n] for n in names if n in by]
    if not hit or not red["window_s"]:
        return None
    return 100.0 * sum(hit) / red["window_s"]


def readings(red: Optional[Dict], kv_rows: Optional[Dict]) -> Dict:
    """What the program's spans, scopes and counters say, each left out
    where there is nothing to read:

    - ``decode_attn_ms``, ``decode_moe_ms``: device ms per decode-program
      call in leaf ops under the model's ``attention`` (``kv_write``
      included) or ``moe`` scope, on the base of the harness's
      ``decode_device_ms.tput``;
    - ``idle_telemetry_share``, ``idle_sample_share``,
      ``idle_admit_share``: device-idle time under ``serving.telemetry``,
      ``serving.sample``, or the admission spans (``ADMIT``), in % of the
      traced window, parts of the harness's ``idle_share.tput``;
    - ``kv_useful_share``: the engine's ``kv_rows_live`` over
      ``kv_rows_read`` (``kv_rows``: their change over the window), in %.
    """
    out = {}
    p = red and red["programs"].get("decode")
    if p and p["count"]:
        for key, scope in (("decode_attn_ms", "attention"),
                           ("decode_moe_ms", "moe")):
            s = scope_s(red, "decode", scope)
            if s is not None:
                out[key] = 1e3 * s / p["count"]
    for key, names in (("idle_telemetry_share", ["serving.telemetry"]),
                       ("idle_sample_share", ["serving.sample"]),
                       ("idle_admit_share", ADMIT)):
        v = idle_share(red, names)
        if v is not None:
            out[key] = v
    if kv_rows and kv_rows["read"]:
        out["kv_useful_share"] = 100.0 * kv_rows["live"] / kv_rows["read"]
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    from chipbench import run as R

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    a = ap.parse_args(argv)
    try:
        _, cell, cfg, mix = R.load_cell(a.workload)
        R.require_chips(cell["chips"])
    except R.Refused as e:
        R.log(f"refused: {e}")
        return 2
    R.use_compile_cache()
    counter = R.CompileCounter()
    _, eng = R.build(cfg, a.seed)
    vocab = cfg["model"]["vocab_size"]
    R.warm_up(eng, mix, vocab)
    kv0 = (eng.kv_rows_read, eng.kv_rows_live)
    d = tempfile.mkdtemp(prefix="chipbench-spans-")
    try:
        rec = R.serve(eng, mix, vocab, a.seed, a.seconds, counter, d)
        red = reduce(events(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)
    kv = {"read": eng.kv_rows_read - kv0[0], "live": eng.kv_rows_live - kv0[1]}
    out = {"workload": a.workload, "seed": a.seed,
           "tokens_in_window": rec["tokens_in_window"],
           "window_s": rec["window_s"],
           "compiles_in_window": rec["compiles_in_window"],
           "readings": readings(red, kv), "kv_rows": kv}
    if red is not None:
        dec = red["programs"].get("decode") or {}
        out.update(
            traced_window_s=red["window_s"], busy_s=red["busy_s"],
            decode_calls=dec.get("count"), decode_device_s=dec.get("device_s"),
            scopes=red["scopes"], idle_by_span=red["idle_by_span"],
            idle_gaps=red["idle_gaps"], device_ops=red["device_ops"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
