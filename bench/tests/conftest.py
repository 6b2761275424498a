"""The benchmark's tests run by explicit path (``python -m pytest
bench/tests``), never in tier-1; they import the harness the way
``bench/run.py`` does."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
