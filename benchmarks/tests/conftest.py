"""Run by hand: ``pytest benchmarks/tests`` (CPU; not part of tier-1)."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the harness modules are siblings of run.py; the program is used from the
# checkout's root
sys.path[:0] = [p for p in (BENCH, os.path.dirname(BENCH))
                if p not in sys.path]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
