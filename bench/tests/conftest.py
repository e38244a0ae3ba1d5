"""Tests of the benchmark itself, on the CPU at smoke size:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

(The repository's `pytest.ini` collects only `tests/`, so these run by
path.)"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
