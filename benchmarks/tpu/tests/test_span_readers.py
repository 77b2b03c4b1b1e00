"""The readers of the program's spans (``metrics/*`` over ``spans.py``),
on hand-made records and device intervals, and the cases in which each
finds nothing to read."""
from types import SimpleNamespace

import pytest

import bench
import spans
from repro.utils import timing
from repro.utils.timing import Record

DEV = "/device:TPU:0"
MS = 1_000_000                      # ns


def load(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def rec(i, name, start_ms, end_ms, parent=None, **attrs):
    return Record(i, parent, "t", name, int(start_ms * MS),
                  int(end_ms * MS), attrs)


def summary(t0_ms, t1_ms, busy_ms):
    """A trace summary: the window and device 0's op intervals (ms on
    the trace's clock, which here runs 1,000 ms ahead of the spans')."""
    iv = [(s * MS, e * MS) for s, e in busy_ms]
    return {"window_ns": (t0_ms * MS, t1_ms * MS), "devices": 1,
            "per_device": {DEV: {"ops": [], "intervals": iv}}}


@pytest.fixture
def program(monkeypatch):
    """Replace the program's span buffer with the records given."""
    state = {"recs": [], "dropped": 0}
    monkeypatch.setattr(timing, "recorded", lambda: list(state["recs"]))
    monkeypatch.setattr(timing, "dropped", lambda: state["dropped"])
    return state


# training: spans on their own clock start at 0 ms; the trace window
# opens at 1,000 ms, with train.fit
TRAIN = [
    rec(0, "train.fit", 0, 100, steps=2),
    rec(1, "train.view_wait", 0, 30, 0, view=0),
    rec(2, "train.dispatch", 30, 31, 0),
    rec(3, "train.view_wait", 31, 33, 0, view=1),
    rec(4, "train.dispatch", 33, 34, 0),
    rec(5, "prefetch.build", 1, 21, None, view=0),
    rec(6, "view.sample", 1, 5, 5),
    rec(7, "view.stage", 8, 20, 5, plan_lanes=512, live_edges=4),
    rec(8, "prefetch.build", 2, 12, None, view=1),
    rec(9, "view.stage", 3, 11, 8, plan_lanes=512, live_edges=12),
]
# device busy 1,010-1,020 (inside the first wait) and 1,030-1,100
TRAIN_TRACE = summary(1000, 1100, [(1010, 1020), (1030, 1100)])


def train_ctx():
    return {"driver": object(), "trace": TRAIN_TRACE}


def test_train_readers(program):
    program["recs"] = TRAIN
    ctx = train_ctx()
    # waits 30 + 2 ms over 2 steps
    assert load("view_wait_ms.train").read(ctx) == pytest.approx(16.0)
    # builds of 20 and 10 ms
    assert load("view_build_ms.train").read(ctx) == pytest.approx(15.0)
    # idle inside the waits: 20 ms of the first (1,000-1,030 less 10
    # busy), none of the second (1,031-1,033, busy), over a 100 ms window
    assert load("idle_in_view_wait.train").read(ctx) == pytest.approx(20.0)
    # 1,024 lanes over 16 live edges
    assert load("sum_stage_lanes_per_edge.train").read(ctx) == \
        pytest.approx(64.0)


# serving: serve_open_loop's t0 is 0.05 s after its window start, which is
# 0 ms on the spans' clock and 1,000 ms on the trace's
SERVE = [
    rec(0, "serve.batch", 10, 40, batch=0, requests=3, misses=0),
    rec(1, "serve.cover", 11, 12, 0),
    rec(2, "serve.device", 15, 35, 0, path="hit"),
    rec(3, "serve.stage", 12, 15, 0),
    rec(4, "view.stage", 13, 14, 3, plan_lanes=1000, live_edges=5),
    rec(5, "serve.batch", 50, 90, batch=1, requests=4, misses=2),
    rec(6, "serve.device", 55, 65, 5, path="full"),
    rec(7, "serve.device", 70, 80, 5, path="hit"),
    rec(8, "view.stage", 52, 54, 5, plan_lanes=3000, live_edges=15),
    rec(9, "serve.collect", 40, 50),
    # after the window: left out
    rec(10, "serve.batch", 250, 260, batch=2, requests=1, misses=1),
]
SERVE_TRACE = summary(1000, 1200, [(1015, 1035), (1055, 1065),
                                   (1070, 1080), (1095, 1100)])


def serve_ctx():
    return {"driver": SimpleNamespace(t0=0.05), "trace": SERVE_TRACE}


def test_serve_readers(program):
    program["recs"] = SERVE
    ctx = serve_ctx()
    # host time: 30 - 20 and 40 - 20 ms
    assert load("batch_host_ms.serve").read(ctx) == pytest.approx(15.0)
    # one of two batches missed
    assert load("miss_path_share.serve").read(ctx) == pytest.approx(50.0)
    # idle inside the batches: 10 + 20 ms over a 200 ms window (the busy
    # time between batches does not count)
    assert load("idle_in_batch.serve").read(ctx) == pytest.approx(15.0)
    assert load("sum_stage_lanes_per_edge.serve").read(ctx) == \
        pytest.approx(4000 / 20)


TRAIN_METRICS = ["view_wait_ms.train", "view_build_ms.train",
                 "idle_in_view_wait.train",
                 "sum_stage_lanes_per_edge.train"]
SERVE_METRICS = ["batch_host_ms.serve", "idle_in_batch.serve",
                 "miss_path_share.serve", "sum_stage_lanes_per_edge.serve"]
ALL = ([(m, TRAIN, train_ctx) for m in TRAIN_METRICS]
       + [(m, SERVE, serve_ctx) for m in SERVE_METRICS])


@pytest.mark.parametrize("name,recs,ctx", ALL, ids=[a[0] for a in ALL])
def test_nothing_to_read(program, monkeypatch, name, recs, ctx):
    reader = load(name)
    program["recs"] = recs
    assert reader.read(ctx()) is not None
    # an empty buffer
    program["recs"] = []
    assert reader.read(ctx()) is None
    # a buffer that dropped records: part of the window is missing
    program["recs"], program["dropped"] = recs, 1
    assert reader.read(ctx()) is None
    program["dropped"] = 0
    # no trace of the window
    assert reader.read({**ctx(), "trace": None}) is None
    # no anchor: no train.fit, or a driver without its window start
    if name.endswith(".train"):
        program["recs"] = [r for r in recs if r.name != "train.fit"]
        assert reader.read(ctx()) is None
        program["recs"] = recs + [rec(99, "train.fit", 200, 300, steps=1)]
        assert reader.read(ctx()) is None
    else:
        assert reader.read({**ctx(), "driver": object()}) is None
    # a program without the recorder (the one before it)
    program["recs"] = recs
    monkeypatch.delattr(timing, "recorded")
    assert reader.read(ctx()) is None


def test_lanes_need_a_plan(program):
    program["recs"] = [r._replace(attrs={**r.attrs, "plan_lanes": 0})
                       if r.name == "view.stage" else r for r in TRAIN]
    assert load("sum_stage_lanes_per_edge.train").read(train_ctx()) is None


def test_idle_inside_merges_overlapping_spans():
    s = summary(0, 100, [(10, 20)])
    recs = [rec(0, "a", 0, 30), rec(1, "b", 25, 50), rec(2, "c", 90, 150)]
    # inside: 0-50 (10 busy) and 90-100 clipped to the window
    assert spans.idle_inside_ns(recs, 0, s) == pytest.approx(50 * MS)
    assert spans.merge([(5, 9), (0, 3), (2, 4), (9, 9)]) == [[0, 4], [5, 9]]
