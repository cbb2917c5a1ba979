"""The benchmark's own tests run on the CPU, at tiny sizes.

    python -m pytest -q chipbench/tests

They are not part of the repository's tier-1 suite (``testpaths =
["tests"]``).  Nothing here needs the chip: the tests that drive a run step
around the harness's look for one themselves.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
