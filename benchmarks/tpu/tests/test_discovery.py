"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files, and an entry in BENCHMARK.json, are found by name: no file
of the harness is edited."""
import json
import shutil

import pytest

import bench
import run


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    root = tmp_path
    here = root / "benchmarks" / "tpu"
    shutil.copytree(bench.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((here / "configs" / "gat_e-alipay.json").read_text())
    cfg["name"] = "gat_e-other"
    (here / "configs" / "gat_e-other.json").write_text(json.dumps(cfg))
    (here / "traffic" / "mini-train-small.json").write_text(json.dumps(
        {"driver": "train_compact", "batch_nodes": 256, "neighbor_cap": 5,
         "lr": 0.005, "weight_decay": 0.0005}))
    (here / "limits" / "gat_e-other.mini-train-small.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    (here / "metrics" / "steps.train.py").write_text(
        "def read(ctx):\n    return ctx['driver'].n_steps\n")
    b["workloads"].append({"name": "gat_e-other.mini-train-small",
                           "config": "gat_e-other",
                           "traffic": "mini-train-small", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "train_nodes_per_s":
            m["workloads"].append("gat_e-other.mini-train-small")
    b["per_layer"].append({"name": "steps.train", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "device", "moves": "train_nodes_per_s",
                           "workloads": ["gat_e-other.mini-train-small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(bench, "HERE", here)
    monkeypatch.setattr(bench, "ROOT", root)

    cell = bench.find_cell("gat_e-other.mini-train-small")
    assert cell["config_data"]["name"] == "gat_e-other"
    assert cell["traffic_data"]["batch_nodes"] == 256
    assert [m["name"] for m in cell["per_layer"]] == ["steps.train"]
    assert "train_nodes_per_s" in [m["name"] for m in cell["end_to_end"]]
    driver = run.make_driver(cell, 1, 1.0)
    assert type(driver).__module__.endswith("train_compact")

    class Fake:
        n_steps = 7
    assert run.per_layer(cell, {"driver": Fake()}) == {
        "steps.train": {"value": 7.0, "unit": "steps"}}


def test_every_listed_name_has_its_files():
    b = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = bench.find_cell(w["name"], b)
        driver = cell["traffic_data"]["driver"]
        assert (bench.HERE / "drivers" / f"{driver}.py").is_file()
        model = cell["config_data"]["model"]["model"]
        assert (bench.HERE / "costs" / f"{model}.py").is_file()
        assert (bench.HERE / "reference" / f"{model}.py").is_file()
    for m in b["per_layer"]:
        assert (bench.HERE / "metrics" / f"{m['name']}.py").is_file()
    for c in b["configs"]:
        assert (bench.ROOT / c["file"]).is_file()


def test_a_listed_metric_that_reads_nothing_fails_the_run():
    cell = bench.find_cell("gat_e-alipay.mini-train")
    ctx = {"driver": object(), "trace": None}
    with pytest.raises(bench.BenchError, match="found nothing to read"):
        run.per_layer(cell, ctx)
    assert run.per_layer(cell, ctx, require=False) == {}
