"""Shared plumbing of the chip benchmark: where its files are, how a cell
is looked up by name, seeds, the device check, the peak table, the
compile cache, and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
``run.py`` finds each of those as a file of its own under this
directory, so a later cell or metric is added as new files.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]                 # the checkout: BENCHMARK.json, src/


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown name, a
    compile inside the window). The run exits non-zero and prints no
    result line."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path, name: str | None = None):
    """Import a benchmark file by path (metric readers are named after
    their metric, dots included, so they are not importable by name)."""
    path = Path(path)
    if not path.is_file():
        raise BenchError(f"no such benchmark file: {path}")
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str, benchmark: dict | None = None) -> dict:
    """The cell's entry in BENCHMARK.json plus its configuration, traffic
    and limits files, all found by name."""
    benchmark = benchmark or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    cell["config_data"] = load_json(HERE / "configs" / f"{cell['config']}.json")
    cell["traffic_data"] = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    cell["limits"] = load_json(HERE / "limits" / f"{name}.json")
    cell["end_to_end"] = [m for m in benchmark["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in benchmark["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def sub_seeds(seed: int, n: int) -> list:
    """``n`` independent 31-bit seeds from one run seed of any size (the
    program's RNGs take 31-bit ints; jax keys take 32-bit ones)."""
    import numpy as np
    state = np.random.SeedSequence(int(seed)).generate_state(n, np.uint32)
    return [int(s) & 0x7FFFFFFF for s in state]


def peaks_for(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json "
                         f"({sorted(table['devices'])}); add it with a source")
    return table["devices"][device_kind]


def require_accelerator(chips: int) -> None:
    """Fail unless JAX sees a TPU with at least ``chips`` devices. Tests
    that rehearse a driver on the CPU replace this function."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")


def setup_jax(precision: str) -> None:
    """The program's compile cache (``repro.utils.compile_cache``: the
    environment's directory, else a fixed ``<checkout>/.jax_cache``),
    every program cached, and the matmul precision the configuration
    states. Called before the first compile."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision", precision)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100); inf counts as a miss."""
    xs = sorted(values)
    if not xs:
        raise BenchError("percentile of no values")
    k = max(0, min(len(xs) - 1, int(-(-q * len(xs) // 100)) - 1))
    return xs[k]


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the one result line, with ``checks`` last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)
