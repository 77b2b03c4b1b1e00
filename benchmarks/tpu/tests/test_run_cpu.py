"""Without a TPU the benchmark exits non-zero and prints no result; so
does a checkout that holds only the benchmark, without the program."""
import json
import os
import shutil
import subprocess
import sys

import bench

ARGS = ["--workload", "gat_e-alipay.mini-train", "--seed", "5",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    for line in out.strip().splitlines()[-1:]:
        try:
            return "correct" not in json.loads(line)
        except ValueError:
            return True
    return True


def test_exits_nonzero_on_cpu():
    p = _run(bench.ROOT, bench.HERE / "run.py")
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(bench.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "benchmarks" / "tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, tmp_path / "benchmarks" / "tpu" / "run.py")
    assert p.returncode != 0
    assert _no_result(p.stdout)
