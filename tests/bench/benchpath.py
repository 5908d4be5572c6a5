"""Puts ``bench/`` and the program on the import path for the harness's tests."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
