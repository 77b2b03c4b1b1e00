"""The reduction from trace events to numbers: on hand-made events, and
on a small trace recorded on a v5e chip (tests/fixtures/)."""
import json
from pathlib import Path

import pytest

import trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_mini_train.json"


def ev(plane, name, s, e, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "text": name,
            "start_ns": float(s), "end_ns": float(e)}


def test_union_and_gaps():
    assert tr.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert tr._gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_summary_by_hand():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    events = [
        ev("/host:CPU", tr.WINDOW, 100, 1100, line="python"),
        ev("/host:CPU", "stage view", 150, 400, line="python"),
        ev(d0, "fusion.1", 0, 200),           # clipped to 100..200
        ev(d0, "_edge_softmax_kernel", 400, 700),
        ev(d0, "all-reduce.2", 650, 900),     # 200 ns alone
        ev(d1, "fusion.1", 100, 600),
        ev(d1, "all-reduce.2", 600, 700),     # 100 ns alone
        ev(d0, "fusion.9", 2000, 2100),       # outside the window
    ]
    s = tr.summarize(events, chips=2)
    assert s["window_s"] == pytest.approx(1e-6)
    assert s["devices"] == 2
    # busy: d0 = 100 + 500 = 600 ns; d1 = 600 ns
    assert s["busy_s"] == pytest.approx(600e-9)
    assert tr.op_time_s(s, "_edge_softmax_kernel") == pytest.approx(150e-9)
    gaps = s["breakdown"]["idle_gaps"]
    assert sorted(gaps) == [["idle host", pytest.approx(200e-9)],
                            ["stage view", pytest.approx(200e-9)]]


def test_recorded_v5e_trace():
    data = json.loads(FIXTURE.read_text())
    s = tr.summarize(data["events"], chips=1)
    want = data["expected"]
    assert s["window_s"] == pytest.approx(want["window_s"])
    assert s["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < s["busy_s"] <= s["window_s"]
    assert tr.op_time_s(s, want["kernel_pattern"]) == pytest.approx(
        want["kernel_s"])
    assert [k for k, _ in s["breakdown"]["device_ops"]] == want["top_ops"]
