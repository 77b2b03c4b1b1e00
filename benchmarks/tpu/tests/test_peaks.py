"""The peak table: v5e is there with its source; an unknown device kind
is refused, never defaulted."""
import pytest

import bench


def test_v5e_peaks():
    p = bench.peaks_for("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert bench.load_json(bench.HERE / "peaks.json")["source"]


def test_unknown_device_kind_is_refused():
    with pytest.raises(bench.BenchError, match="not in peaks.json"):
        bench.peaks_for("TPU v9 imaginary")
