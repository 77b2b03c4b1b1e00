"""The benchmark's own tests, run by path:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/tpu/tests
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
