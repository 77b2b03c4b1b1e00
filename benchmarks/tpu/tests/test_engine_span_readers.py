"""The readers of the engine ``Trainer``'s staging spans
(``engine_stage_ms.train``, ``engine_staged_mb.train``), on hand-made
records, and the cases in which each finds nothing to read."""
import pytest

import bench
from repro.utils import timing
from repro.utils.timing import Record

MS = 1_000_000                      # ns


def load(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def rec(i, name, start_ms, end_ms, parent=None, **attrs):
    return Record(i, parent, "t", name, int(start_ms * MS),
                  int(end_ms * MS), attrs)


@pytest.fixture
def program(monkeypatch):
    """Replace the program's span buffer with the records given."""
    state = {"recs": [], "dropped": 0}
    monkeypatch.setattr(timing, "recorded", lambda: list(state["recs"]))
    monkeypatch.setattr(timing, "dropped", lambda: state["dropped"])
    return state


# spans on their own clock start at 0 ms; the trace window opens at
# 1,000 ms, with train.fit. Two window steps, each staged by a worker;
# a third build starts after the window and is left out
ENGINE = [
    rec(0, "train.fit", 0, 100, steps=2),
    rec(1, "train.view_wait", 0, 30, 0, view=0),
    rec(2, "train.dispatch", 30, 31, 0),
    rec(3, "train.view_wait", 31, 33, 0, view=1),
    rec(4, "train.dispatch", 33, 34, 0),
    rec(5, "prefetch.build", 1, 21, None, view=0),
    rec(6, "view.sample", 1, 2, 5),
    rec(7, "engine.shard", 2, 6, 5),
    rec(8, "engine.stage", 6, 20, 5, staged_bytes=3_000_000),
    rec(9, "prefetch.build", 22, 32, None, view=1),
    rec(10, "view.sample", 22, 23, 9),
    rec(11, "engine.shard", 23, 25, 9),
    rec(12, "engine.stage", 25, 31, 9, staged_bytes=5_000_000),
    rec(13, "prefetch.build", 150, 160, None, view=2),
    rec(14, "engine.shard", 150, 151, 13),
    rec(15, "engine.stage", 151, 159, 13, staged_bytes=7_000_000),
]
TRACE = {"window_ns": (1000 * MS, 1100 * MS), "devices": 1,
         "per_device": {"/device:TPU:0": {"ops": [], "intervals": []}}}


def ctx():
    return {"driver": object(), "trace": TRACE}


def test_engine_readers(program):
    program["recs"] = ENGINE
    # shard 4 + 2 ms, stage 14 + 6 ms, over 2 steps
    assert load("engine_stage_ms.train").read(ctx()) == pytest.approx(13.0)
    # 8 MB over 2 steps
    assert load("engine_staged_mb.train").read(ctx()) == pytest.approx(4.0)


METRICS = ["engine_stage_ms.train", "engine_staged_mb.train"]


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read(program, monkeypatch, name):
    reader = load(name)
    program["recs"] = ENGINE
    assert reader.read(ctx()) is not None
    # an empty buffer
    program["recs"] = []
    assert reader.read(ctx()) is None
    # a buffer that dropped records: part of the window is missing
    program["recs"], program["dropped"] = ENGINE, 1
    assert reader.read(ctx()) is None
    program["dropped"] = 0
    # no trace of the window
    assert reader.read({**ctx(), "trace": None}) is None
    # no anchor: no train.fit, or two of them
    program["recs"] = [r for r in ENGINE if r.name != "train.fit"]
    assert reader.read(ctx()) is None
    program["recs"] = ENGINE + [rec(99, "train.fit", 200, 300, steps=1)]
    assert reader.read(ctx()) is None
    # a training path that stages no engine views (the compact trainer)
    program["recs"] = [r for r in ENGINE if not r.name.startswith("engine.")]
    assert reader.read(ctx()) is None
    # a program without the recorder
    program["recs"] = ENGINE
    monkeypatch.delattr(timing, "recorded")
    assert reader.read(ctx()) is None


def test_staged_mb_needs_its_counter(program):
    program["recs"] = [r._replace(attrs={}) if r.name == "engine.stage"
                       else r for r in ENGINE]
    assert load("engine_staged_mb.train").read(ctx()) is None
    assert load("engine_stage_ms.train").read(ctx()) is not None
