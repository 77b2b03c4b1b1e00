"""Tiny versions of the cells for CPU rehearsals: the harness's look for
a chip and its peak table are replaced, and each configuration and
traffic mix is cut to a size the CPU runs in seconds."""
import bench

PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def tiny_cell(name: str) -> dict:
    cell = bench.find_cell(name)
    cfg, t = cell["config_data"], cell["traffic_data"]
    cfg["num_nodes"] = 600
    if "batch_nodes" in t:
        t["batch_nodes"] = 64
    if "hot_nodes" in t:
        t.update(hot_nodes=64, rate_per_s=20, clients=8)
    return cell


def allow_cpu(monkeypatch, cell: dict):
    monkeypatch.setattr(bench, "require_accelerator", lambda chips: None)
    monkeypatch.setattr(bench, "peaks_for", lambda kind: PEAKS)
    monkeypatch.setattr(bench, "find_cell", lambda name, b=None: cell)


def args(name: str, trace: int = 0, seconds: float = 2.0):
    import run
    return run.parse(["--workload", name, "--seed", str(2**31 + 77),
                      "--seconds", str(seconds), "--trace", str(trace)])
