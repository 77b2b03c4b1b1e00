"""With the timed path broken underneath, ``correct`` comes out false:
for each fault a cell can have (faults.py), through a whole CPU run."""
import pytest

import faults
import run
from tiny import allow_cpu, args, tiny_cell

CASES = [("gat_e-alipay.mini-train", "half_batch"),
         ("gat_e-alipay.mini-train", "frozen_step"),
         ("gat_e-alipay.serve-hot", "altered_answer")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_caught(monkeypatch, name, fault):
    cell = tiny_cell(name)
    allow_cpu(monkeypatch, cell)
    undo = faults.FAULTS[fault]()
    try:
        result, checks = run.run_cell(args(name))
    finally:
        undo()
    assert not result["correct"], checks

