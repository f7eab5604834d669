"""Tests of the benchmark itself (not of the program): run with
``JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest bench/tests``."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
