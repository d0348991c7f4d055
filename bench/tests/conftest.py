"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They import the benchmark as the package ``bench`` and the program from
``src/``, both from the checkout's root.
"""
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (str(ROOT), str(ROOT / "src"), str(ROOT / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
