"""Tests of the on-chip benchmark's harness, run on the CPU by path:

    python -m pytest benchmarks/onchip/tests

They import the system from ``src/`` and the harness from this directory.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))
