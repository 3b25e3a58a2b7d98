"""CPU tests of the benchmark (``python -m pytest bench/tests``).

They import the harness from the checkout; JAX is held to the CPU, where
the Pallas kernel runs in interpret mode.
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
