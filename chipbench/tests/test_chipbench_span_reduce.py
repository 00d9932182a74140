"""The program's spans and scopes in a trace, on traces with known
answers: a hand-made one (``data/trace_spans.json``), the harness's older
traces without them, and one recorded on a v5e
(``data/trace_spans_v5e.json``, made by ``record_v5e_trace.py``)."""
import json
from pathlib import Path

import pytest

from chipbench import span_reduce, trace_reduce

DATA = Path(__file__).parent / "data"
PROGRAMS = span_reduce.PROGRAMS
NEW_KEYS = {"scopes", "idle_by_span"}


def load(name):
    return json.loads((DATA / name).read_text())


@pytest.fixture
def spans():
    return span_reduce.reduce(load("trace_spans.json"))


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_decode_impl)/jit(main)/while/body/closed_call/attention/"
     "kv_write/scatter", "attention/kv_write"),
    ("jit(_decode_impl)/while/body/closed_call/moe/dispatch/"
     "jit(searchsorted)/vmap()/while/body/lt", "moe/dispatch"),
    ("jit(_decode_impl)/while/body/closed_call/moe/experts/"
     "tbd,tdf->tbf/dot_general", "moe/experts"),
    ("jit(_decode_impl)/head/dot_general;jit(_decode_impl)/embed/add",
     "head"),
    ("jit(_decode_impl)/while/body/dynamic_slice", "(unscoped)"),
    ("", "(unscoped)"),
])
def test_scope_of_op_name(op_name, scope):
    assert span_reduce.scope_of(op_name) == scope


@pytest.mark.parametrize("name", ["trace_small.json", "trace_v5e.json",
                                  "trace_spans.json", "trace_spans_v5e.json"])
def test_keeps_trace_reduce_answers(name):
    """Every answer of ``trace_reduce.reduce`` stands; a gap's name only
    gains its program span after a ``/``."""
    ev = load(name)
    got = span_reduce.reduce(ev)
    want = trace_reduce.reduce(span_reduce._harness_view(ev), PROGRAMS)
    assert {k: v for k, v in got.items() if k not in NEW_KEYS
            and k != "idle_gaps"} == {k: v for k, v in want.items()
                                      if k != "idle_gaps"}
    assert [g for _, g in got["idle_gaps"]] == [g for _, g in
                                                 want["idle_gaps"]]
    for (n, _), (w, _) in zip(got["idle_gaps"], want["idle_gaps"]):
        assert n == w or n.startswith(w + "/serving.")


def test_scopes_count_leaf_ops_once(spans):
    # the while ops hold the layer loop's ops and count for nothing
    dec = spans["scopes"]["decode"]
    assert dec == pytest.approx({
        "attention": 0.05 + 0.08, "attention/kv_write": 0.02,
        "moe/experts": 0.05 + 0.05, "moe/router": 0.03, "head": 0.02,
        "(unscoped)": 0.03 + 0.07})
    assert list(dec)[0] == "attention"
    assert sum(dec.values()) == pytest.approx(
        spans["programs"]["decode"]["device_s"])
    assert spans["scopes"]["prefill"] == pytest.approx({"attention": 0.1})
    assert "insert" not in spans["scopes"]


def test_idle_split_over_innermost_program_spans(spans):
    idle = spans["window_s"] - spans["busy_s"]
    assert idle == pytest.approx(0.455)
    assert spans["idle_by_span"] == pytest.approx({
        "(none)": 0.02 + 0.045 + 0.03, "serving.step": 0.005 + 0.01,
        "serving.admit": 0.015 + 0.01,
        "serving.prefill": 0.005 + 0.01 + 0.02, "serving.kv_insert": 0.005,
        "serving.device_wait": 0.0, "serving.telemetry": 0.05 + 0.06 + 0.03,
        "serving.decode": 0.04 + 0.04, "serving.sample": 0.02 + 0.04})
    assert sum(spans["idle_by_span"].values()) == pytest.approx(idle)


def test_gaps_named_by_harness_and_program_span(spans):
    assert [n for n, _ in spans["idle_gaps"]] == [
        "engine_step/serving.telemetry", "engine_step/serving.sample",
        "engine_step/serving.telemetry", "engine_step/serving.admit",
        "bookkeeping", "engine_step/serving.decode",
        "engine_step/serving.prefill"]
    assert [g for _, g in spans["idle_gaps"]] == pytest.approx(
        [0.13, 0.10, 0.08, 0.05, 0.045, 0.04, 0.01])


def test_readings_of_the_span_trace(spans):
    got = span_reduce.readings(spans, {"read": 1000, "live": 550})
    assert got == pytest.approx({
        "decode_attn_ms": 75.0, "decode_moe_ms": 65.0,
        "idle_telemetry_share": 14.0, "idle_sample_share": 6.0,
        "idle_admit_share": 6.5, "kv_useful_share": 55.0})
    # parts of the harness's decode_device_ms.tput and idle_share.tput
    dec = spans["programs"]["decode"]
    assert got["decode_attn_ms"] + got["decode_moe_ms"] <= \
        1e3 * dec["device_s"] / dec["count"]
    idle = 100.0 * (1 - spans["busy_s"] / spans["window_s"])
    assert sum(got[k] for k in ("idle_telemetry_share", "idle_sample_share",
                                "idle_admit_share")) <= idle


def test_a_program_without_spans_or_scopes_reads_nothing_new():
    """The harness's older traces (no program spans, no op scopes): the
    readings are empty, and the old answers stand."""
    r = span_reduce.reduce(load("trace_small.json"))
    assert r["idle_by_span"] == pytest.approx({"(none)": 1.2 - 0.82})
    assert set(r["scopes"]["decode"]) == {"(unscoped)"}
    assert r["idle_gaps"][4][0] == "bookkeeping"
    assert span_reduce.readings(r, None) == {}
    assert span_reduce.readings(None, {"read": 0, "live": 0}) == {}


def test_no_device_work_in_window_reads_nothing():
    ev = load("trace_spans.json")
    ev["host"] = [["chipbench.window", 3.0, 1.0]]
    assert span_reduce.reduce(ev) is None


def test_recorded_v5e_trace_with_spans_and_scopes():
    """A tiny engine's step on the chip (``record_v5e_trace.py``): the
    model's scopes are found in both programs, the leaf ops hold nearly
    all of a program's time and no more, and every idle gap lies under a
    program span."""
    r = span_reduce.reduce(load("trace_spans_v5e.json"))
    model = {"embed", "attention", "moe/router", "moe/dispatch",
             "moe/experts", "moe/combine", "head"}
    assert model <= set(r["scopes"]["prefill"])
    assert model | {"attention/kv_write"} <= set(r["scopes"]["decode"])
    for prog in ("decode", "prefill"):
        leaves = sum(r["scopes"][prog].values())
        assert 0.9 < leaves / r["programs"][prog]["device_s"] <= 1.0
    spans = {"serving." + n for n in ("step", "admit", "prefill", "kv_insert",
                                      "decode", "device_wait", "telemetry",
                                      "sample")}
    assert spans <= set(r["idle_by_span"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert all(n.startswith("engine_step/serving.")
               for n, _ in r["idle_gaps"])
    got = span_reduce.readings(r, None)
    assert set(got) == {"decode_attn_ms", "decode_moe_ms",
                        "idle_telemetry_share", "idle_sample_share",
                        "idle_admit_share"}
    assert got["decode_attn_ms"] + got["decode_moe_ms"] <= \
        1e3 * r["programs"]["decode"]["device_s"]
