"""A CPU rehearsal of each driver at a tiny size, end to end through
``run.run_cell``: set-up, window, reference and the result line. Only
here is the CPU accepted (tests/tiny.py)."""
import json

import pytest

import bench
import run
from tiny import allow_cpu, args, tiny_cell

CELLS = ["gat_e-alipay.mini-train", "gat_e-alipay.serve-hot"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_driver_rehearsal(monkeypatch, capsys, name, trace):
    cell = tiny_cell(name)
    allow_cpu(monkeypatch, cell)
    result, checks = run.run_cell(args(name, trace))
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    want = cell["per_layer"] if trace else cell["end_to_end"]
    got = set(result["metrics"])
    if trace:
        # device-trace readers find no device on the CPU and stay silent
        assert got <= {m["name"] for m in want}
        assert {"busy_s", "window_s"} <= set(result["device"])
    else:
        assert got == {m["name"] for m in want}
    bench.emit(result, checks)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == set(cell["limits"]["limits"])
