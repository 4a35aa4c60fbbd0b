"""CPU rehearsal tests of the benchmark, run by explicit path:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

Tiny configurations stand in for the cells' own; nothing here is timed."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
