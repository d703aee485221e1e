"""The benchmark's tests import it as ``benchmarks.chip`` from the root
of the checkout, and the program from ``src``.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
