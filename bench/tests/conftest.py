"""Puts the benchmark's own modules (``harness``) and the program (``src``)
on the path for the benchmark's CPU tests."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
