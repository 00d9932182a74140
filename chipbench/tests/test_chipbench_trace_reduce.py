"""The trace reduction on recorded traces with known answers."""
import json
from pathlib import Path

import pytest

from chipbench import trace_reduce

DATA = Path(__file__).parent / "data"
PROGRAMS = {"decode": "_decode_impl", "prefill": "_prefill_impl"}


def test_small_trace_busy_idle_and_programs():
    ev = json.loads((DATA / "trace_small.json").read_text())
    r = trace_reduce.reduce(ev, PROGRAMS)
    # window [0.9, 2.1]; busy: [1.0,1.1] [1.2,1.5] [1.55,1.57] [1.6,1.9]
    # and the clipped [2.0,2.1]; the decode at 0.5 lies outside
    assert r["window_s"] == pytest.approx(1.2)
    assert r["busy_s"] == pytest.approx(0.82)
    assert r["programs"]["decode"]["count"] == 3
    assert r["programs"]["decode"]["device_s"] == pytest.approx(0.7)
    assert r["programs"]["prefill"]["count"] == 1
    assert r["programs"]["prefill"]["device_s"] == pytest.approx(0.1)
    ops = dict(r["device_ops"])
    assert ops["fusion.3"] == pytest.approx(0.5)   # 0.2 + 0.2 + 0.1 clipped
    assert ops["copy.1"] == pytest.approx(0.2)
    assert r["device_ops"][0][0] == "fusion.3"
    # idle gaps, longest first, named by the host span covering them
    gaps = r["idle_gaps"]
    assert [round(g, 6) for _, g in gaps] == [0.1, 0.1, 0.1, 0.05, 0.03]
    assert {n for n, _ in gaps[:3]} == {"engine_step"}
    assert gaps[4][0] == "bookkeeping"
    assert sum(g for _, g in gaps) == pytest.approx(1.2 - 0.82)


def test_no_device_work_in_window_reads_nothing():
    ev = json.loads((DATA / "trace_small.json").read_text())
    ev["host"] = [["chipbench.window", 3.0, 1.0]]
    assert trace_reduce.reduce(ev, PROGRAMS) is None
    ev["host"] = []
    assert trace_reduce.reduce(ev, PROGRAMS) is None


def test_recorded_v5e_trace():
    ev = json.loads((DATA / "trace_v5e.json").read_text())
    r = trace_reduce.reduce(ev, PROGRAMS)
    # five calls of each program, recorded on the chip; modules do not
    # overlap, so busy time is their sum
    assert r["programs"]["decode"]["count"] == 5
    assert r["programs"]["prefill"]["count"] == 5
    assert r["programs"]["decode"]["device_s"] == pytest.approx(66.453e-6)
    assert r["programs"]["prefill"]["device_s"] == pytest.approx(39.755e-6)
    assert r["busy_s"] == pytest.approx(66.453e-6 + 39.755e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"][0][0] == "fusion"
    assert {n for n, _ in r["idle_gaps"]} <= {"engine_step", "bookkeeping",
                                              "no host span"}
