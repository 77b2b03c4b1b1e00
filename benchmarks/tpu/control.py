#!/usr/bin/env python3
"""Readings that set a cell's limits (not run by the benchmark's own
runs): for each seed, in one process, the numbers of the program's own
run and those of the control, the plain reference at the precision
below the configuration's (``high`` for float32 at ``highest``) put in
the program's place. ``--fault`` plants one of ``faults.py``'s faults
in the program first. Prints one JSON line per seed.

    python benchmarks/tpu/control.py --workload <cell> --seeds 1 2 3 \
        --seconds 10 [--fault half_batch] [--benchmark <file>]

``--benchmark`` names another file in BENCHMARK.json's format, for a
cell whose files exist but which BENCHMARK.json does not list yet.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import bench  # noqa: E402

# the nearest precision below the one a configuration states
BELOW = {"highest": "high", "high": "bf16"}


def readings(cell: dict, seed: int, seconds: float, control: bool = True):
    import run
    driver = run.make_driver(cell, seed, seconds)
    t = time.perf_counter()
    driver.setup()
    setup_s = time.perf_counter() - t
    serving = not hasattr(driver, "n_steps")
    if serving:
        driver.window()
    driver.release()
    prec = cell["config_data"]["precision"]
    out = {"seed": seed, "setup_s": setup_s,
           "program": driver.compare(prec)}
    if control:
        low = BELOW[prec]
        if serving:
            out["control"] = driver.compare(
                prec, served=driver.reference_logits(low))
        else:
            import check
            out["control"] = check.train_numbers(
                driver.reference_numbers(low), driver.reference_numbers(prec))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--benchmark", default=None)
    args = ap.parse_args(argv)
    cell = bench.find_cell(args.workload, args.benchmark
                           and bench.load_json(args.benchmark))
    bench.require_accelerator(int(cell["chips"]))
    bench.setup_jax(cell["config_data"]["precision"])
    if args.fault:
        import faults
        faults.FAULTS[args.fault]()
    for s in args.seeds:
        r = readings(cell, s, args.seconds, control=args.fault is None)
        r["fault"] = args.fault
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
