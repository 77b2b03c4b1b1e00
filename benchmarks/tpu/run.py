#!/usr/bin/env python3
"""The chip benchmark: runs one cell once and prints one result line.

    python benchmarks/tpu/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

It loads the cell's configuration, traffic and limits by name (see
``bench.find_cell``), builds and warms the system (counted as
``setup_s``), measures for ``--seconds``, reads the device's memory
peak, frees the program's state, compares what the timed path produced
with the plain reference, and prints the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics from a profiler trace of the
window (``--trace 1``). Without a TPU, or with fewer chips than the cell
needs, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def make_driver(cell: dict, seed: int, seconds: float):
    mod = bench.load_module(
        bench.HERE / "drivers" / f"{cell['traffic_data']['driver']}.py")
    return mod.Driver(cell, seed, seconds)


def traced_window(driver, chips: int) -> dict:
    """Run the window under the profiler; returns the trace summary."""
    import jax
    import trace_reduce as bench_trace
    out = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(out)
        try:
            with jax.profiler.TraceAnnotation(bench_trace.WINDOW):
                driver.window()
        finally:
            jax.profiler.stop_trace()
        return bench_trace.summarize(bench_trace.load(out), chips)
    finally:
        shutil.rmtree(out, ignore_errors=True)


def per_layer(cell: dict, ctx: dict, require: bool = True) -> dict:
    """Each per-layer metric the cell lists, from its reader. A reader
    that finds nothing returns None; for a metric this cell lists that
    is an error (a renamed kernel or span must not silence its metric),
    except where ``require`` is off (CPU rehearsals have no device
    trace)."""
    out = {}
    for m in cell["per_layer"]:
        reader = bench.load_module(bench.HERE / "metrics" / f"{m['name']}.py")
        v = reader.read(ctx)
        if v is None:
            if require:
                raise bench.BenchError(
                    f"per-layer metric {m['name']} found nothing to read")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(args, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result dict and its checks."""
    cell = bench.find_cell(args.workload)
    chips = int(cell["chips"])
    bench.require_accelerator(chips)
    config = cell["config_data"]
    bench.setup_jax(config["precision"])
    import jax
    kind = jax.devices()[0].device_kind
    peaks = bench.peaks_for(kind)

    driver = make_driver(cell, args.seed, args.seconds)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    summary = None
    if args.trace:
        summary = traced_window(driver, chips)
    else:
        driver.window()
    device = bench.device_info(chips)
    e2e = driver.e2e()
    attempted, failed = driver.counts()
    ctx = {"driver": driver, "trace": summary, "peaks": peaks,
           "chips": chips, "config": config, "cell": cell,
           "costs": bench.load_module(
               bench.HERE / "costs" / f"{config['model']['model']}.py")}
    layer_metrics = (per_layer(cell, ctx,
                               require=device["platform"] == "tpu")
                     if args.trace else None)
    driver.release()
    numbers = driver.compare(config["precision"])
    from check import judge
    correct, checks = judge(numbers, cell["limits"])

    if args.trace:
        metrics = layer_metrics
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    else:
        e2e["setup_s"] = setup_s
        metrics = {}
        for m in cell["end_to_end"]:
            if m["name"] not in e2e:
                raise bench.BenchError(f"the driver gave no {m['name']}")
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = summary["breakdown"]
    return result, checks


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, checks = run_cell(args)
    except bench.BenchError as e:
        print(f"benchmark error: {e}", file=sys.stderr)
        return 2
    bench.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
