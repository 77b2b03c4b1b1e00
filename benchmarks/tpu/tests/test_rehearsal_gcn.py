"""A CPU rehearsal of ``gcn-reddit.global-train`` at a tiny size, end to
end through ``run.run_cell``: the engine ``Trainer`` under the global
batch against the plain GCN reference, traced and not; and with the
timed path broken underneath, ``correct`` comes out false."""
import pytest

import bench
import faults
import run
from tiny import allow_cpu, args, tiny_cell

NAME = "gcn-reddit.global-train"
# the engine's staging spans, read from this rehearsal's own trace (not
# listed in BENCHMARK.json: a parent without the spans could not read them)
ENGINE_METRICS = ["engine_stage_ms.train", "engine_staged_mb.train"]


def gcn_cell():
    cell = tiny_cell(NAME)
    cell["config_data"]["avg_degree"] = 24       # of 600 nodes
    cell["per_layer"] = cell["per_layer"] + [
        {"name": m, "unit": "x"} for m in ENGINE_METRICS
        if m not in {p["name"] for p in cell["per_layer"]}]
    return cell


@pytest.mark.parametrize("trace", [0, 1])
def test_gcn_rehearsal(monkeypatch, trace):
    cell = gcn_cell()
    allow_cpu(monkeypatch, cell)
    result, checks = run.run_cell(args(NAME, trace))
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(checks) == set(cell["limits"]["limits"])
    if trace:
        got = result["metrics"]
        assert set(ENGINE_METRICS) <= set(got)
        assert got["engine_stage_ms.train"]["value"] > 0
        assert got["engine_staged_mb.train"]["value"] > 0
    else:
        assert set(result["metrics"]) == {m["name"]
                                          for m in cell["end_to_end"]}


def test_gcn_cell_is_listed_for_the_training_metrics():
    cell = bench.find_cell(NAME)
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_nodes_per_s", "setup_s"}
    assert {"device_idle.train", "mfu.train", "sum_stage_ms.train",
            "sum_stage_roofline.train"} <= {m["name"]
                                            for m in cell["per_layer"]}
    assert "sum_stage_lanes_per_edge.train" not in {
        m["name"] for m in cell["per_layer"]}


@pytest.mark.parametrize("fault", ["half_batch", "frozen_step"])
def test_gcn_fault_is_caught(monkeypatch, fault):
    cell = gcn_cell()
    allow_cpu(monkeypatch, cell)
    undo = faults.FAULTS[fault]()
    try:
        result, checks = run.run_cell(args(NAME))
    finally:
        undo()
    assert not result["correct"], checks
