"""Puts the benchmark's directory and the program's ``src`` on the path
for the tests here, which run on the CPU."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(BENCH, "..", "..", "src")):
    p = os.path.normpath(p)
    if p not in sys.path:
        sys.path.insert(0, p)
